"""The control of the comparison: the plain reference put in the program's
place, computed at `precision="high"` (three bf16 passes), the nearest
precision below the float32 at `HIGHEST` the configurations state.  The
window drives it through the same `Scheduler` and the same comparison as
the program; it has to come out as not correct.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from . import reference


class ControlRuntime:
    """A `ServeRuntime` stand-in answering each batch by brute force."""

    def __init__(self, x: np.ndarray):
        self.x = x

    def serve_batch(self, queries, k, with_status=False, **kw):
        ids, dists = reference.brute_force(self.x, np.asarray(queries), k,
                                           precision="high")
        if not with_status:
            return ids, dists
        return ids, dists, SimpleNamespace(degraded=np.zeros(len(ids), bool))


def control_runtime(x, spec, seed, cache_dir, log):
    """`run_cell`'s `serve` for the control: it has no index step."""
    log("backend: control (brute force at precision high)")
    return ControlRuntime(x), 0.0
