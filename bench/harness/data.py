"""Corpus and held-out queries, made from the run's seed by the generator
the configuration names: `bench/data/<data.generator>.py`, whose
`generate(n, d, params, seed)` returns (n, d) f32 rows.  An unknown
generator is an error.
"""
from __future__ import annotations

from pathlib import Path

import numpy as np

from .modules import load_named

GENERATORS = Path(__file__).resolve().parents[1] / "data"


def sub_seed(seed: int, stream: int) -> int:
    """A 32-bit seed for one named use of the run's seed (any int >= 0)."""
    return int(np.random.SeedSequence([seed, stream]).generate_state(1)[0])


def generator_file(config: dict) -> Path:
    return GENERATORS / f"{config['data']['generator']}.py"


def make_corpus(config: dict, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """(corpus (n, d), held-out queries (n_queries, d)), both f32, drawn
    in one call so the queries follow the corpus."""
    n, nq, d = config["n"], config["n_queries"], config["d"]
    gen = load_named(generator_file(config), "data generator")
    both = gen.generate(n + nq, d, config["data"], sub_seed(seed, 0))
    return both[:n], both[n:]
