"""The traffic of a run: the arrival process a workload names, found as
`bench/traffic/<arrivals>.py`, and its parameters from the workload file.

An arrival process module gives
- `plan(params, seconds, n_queries, rng)`: (arrival seconds (N,), held-out
  query index (N,)), made in set-up from the run's seed;
- `drive(sched, requests)`: (completions, requests offered), the window.

An unknown process is an error.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from .data import sub_seed
from .modules import load_named

PROCESSES = Path(__file__).resolve().parents[1] / "traffic"


def process(traffic: dict):
    return load_named(PROCESSES / f"{traffic['arrivals']}.py",
                      "arrival process")


def timeline(traffic: dict, seconds: float, n_queries: int,
             seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(arrival seconds (N,), held-out query index (N,)) for one run."""
    rng = np.random.default_rng(sub_seed(seed, 1))
    return process(traffic).plan(traffic, seconds, n_queries, rng)
