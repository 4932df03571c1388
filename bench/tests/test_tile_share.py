"""The reader of `hop_tile_share`: nothing on a program whose completions
carry no tile counts (the tiny run on the CPU, whose `auto` backend is the
unfused scan), and Σ ceil(b / 8) ÷ (8 × rounds) on a run of known batch
sizes through the streamed kernel in interpret mode."""
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import BENCH
from harness import cell
from harness.modules import load_module

SIZES = (1, 9, 64, 20)      # real rows of each round of the known run
B, TILE = 64, 8


def _read(done):
    return load_module(BENCH / "metrics" / "hop_tile_share.py").read(
        SimpleNamespace(done=done))


def test_reads_nothing_without_tile_counts():
    bare = [SimpleNamespace(latency=0.1, round=0, tier=0)] * 4
    no_tiles = [SimpleNamespace(latency=0.1, round=0, tier=0, tiles=None,
                                tiles_run=None)] * 4
    assert _read(bare) is None
    assert _read(no_tiles) is None


def test_reads_nothing_on_the_tiny_run(run_tiny, monkeypatch):
    kept = []

    class Kept(cell.Record):
        def __init__(self, *args, **kw):
            super().__init__(*args, **kw)
            kept.append(self)
    monkeypatch.setattr(cell, "Record", Kept)
    r = run_tiny(seed=11, trace=True)
    (rec,) = kept
    assert all(c.tiles is None and c.tiles_run is None for c in rec.done)
    assert "hop_tile_share" not in r["metrics"]


def _known_run():
    """Completions of one round per entry of SIZES, each round's requests
    arriving together, served by the streamed kernel on a random graph."""
    from repro.serve import (BatchedANNEngine, BeamTier, EngineConfig,
                             Scheduler, SchedulerConfig, ServeRuntime)
    from repro.serve.runtime import Request

    rng = np.random.default_rng(3)
    n, d, r, m, k = 256, 8, 8, 4, 16
    engine = BatchedANNEngine(
        {"x": rng.normal(size=(n, d)).astype(np.float32),
         "adj": rng.integers(0, n, (n, r)).astype(np.int32),
         "codes": rng.integers(0, k, (n, m)).astype(np.uint8),
         "codebooks": rng.normal(size=(m, k, d // m)).astype(np.float32),
         "entry_cands": np.arange(0, n, 16)},
        EngineConfig(l=16, max_hops=4, backend="fused_stream_interpret"))
    sched = Scheduler(ServeRuntime([np.arange(n)], [engine]),
                      SchedulerConfig(k=10, max_batch=B, slo=1e6,
                                      tiers=(BeamTier(),)))
    queries = rng.normal(size=(B, d)).astype(np.float32)
    reqs = []
    for g, b in enumerate(SIZES):
        reqs += [Request(rid=len(reqs) + j, query=queries[j],
                         arrival=1e4 * g, deadline=1e4 * g + 1e6)
                 for j in range(b)]
    return sched.run(reqs, warmup=False)


def test_equals_tiles_run_over_tiles_on_a_known_run():
    done = _known_run()
    assert sorted({c.round for c in done}) == list(range(len(SIZES)))
    want = sum(-(-b // TILE) for b in SIZES) / (B // TILE * len(SIZES))
    assert _read(done) == pytest.approx(want)
