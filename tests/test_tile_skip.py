"""The serve path skips the hop-loop tiles that hold only padding rows.

`Scheduler` pads each micro-batch to `max_batch` rows and passes the count
of real rows down (`ServeRuntime.serve_batch(rows=)` -> each shard's
`BatchedANNEngine.search_batch(rows=)` -> `beam_hops(n_live=)`).  The real
rows' answers must not change, the counters must say how many tiles ran,
and every fill must run the one compiled program.
"""
import numpy as np
import pytest

from repro.core.engine import BAMGParams
from repro.serve import (BeamTier, EngineConfig, Scheduler, SchedulerConfig,
                         ServeRuntime)
from repro.serve.ann_engine import batched_search
from repro.serve.runtime import Request

K, B, TILE = 10, 64, 8
_CFG = EngineConfig(l=32, max_hops=16, backend="fused_stream_interpret")


@pytest.fixture(scope="module")
def fleet(small_corpus):
    """A two-shard runtime on the streamed kernel, and 64 queries."""
    rt = ServeRuntime.build(small_corpus.base, n_shards=2,
                            params=BAMGParams(r=16, l_build=32, seed=0),
                            config=_CFG)
    rng = np.random.default_rng(5)
    q = (small_corpus.base[rng.choice(len(small_corpus.base), B, False)]
         + rng.normal(0, 0.1, (B, small_corpus.base.shape[1])))
    return rt, q.astype(np.float32)


def _padded(q, b):
    """The batch `Scheduler` sends for the first b queries."""
    return np.concatenate([q[:b], np.tile(q[:1], (B - b, 1))])


@pytest.mark.parametrize("b", (1, 9, 64))
def test_scheduler_skips_padding_tiles(fleet, b):
    rt, q = fleet
    want_ids, want_d = rt.serve_batch(_padded(q, b), K)
    sched = Scheduler(rt, SchedulerConfig(k=K, max_batch=B, slo=10.0,
                                          tiers=(BeamTier(),)))
    done = sched.run([Request(rid=i, query=q[i], arrival=0.0, deadline=10.0)
                      for i in range(b)], warmup=False)
    assert len(done) == b and {c.round for c in done} == {0}
    np.testing.assert_array_equal([c.ids for c in done], want_ids[:b])
    np.testing.assert_array_equal([c.dists for c in done], want_d[:b])
    for c in done:
        assert (c.tiles_run, c.tiles) == (-(-b // TILE), B // TILE)


@pytest.mark.parametrize("b", (1, 9, 64))
def test_runtime_passes_the_count_to_every_shard(fleet, b):
    rt, q = fleet
    ids, d, status = rt.serve_batch(_padded(q, b), K, with_status=True,
                                    rows=b)
    assert (status.tiles_run, status.tiles) == (-(-b // TILE), B // TILE)
    for eng in rt.engines:
        assert (eng.last_tiles_run, eng.last_tiles) == (-(-b // TILE),
                                                        B // TILE)
    want_ids, want_d = rt.serve_batch(_padded(q, b), K)
    np.testing.assert_array_equal(ids[:b], want_ids[:b])
    np.testing.assert_array_equal(d[:b], want_d[:b])
    # the rows of a skipped tile come back empty from both shards
    ran = -(-b // TILE) * TILE
    assert (ids[ran:] == -1).all() and np.isinf(d[ran:]).all()
    # without a count every tile runs
    assert [e.last_tiles_run for e in rt.engines] == [B // TILE] * 2


def test_every_fill_runs_one_program(fleet):
    rt, q = fleet
    rt.serve_batch(_padded(q, 3), K, rows=3)
    compiled = batched_search._cache_size()
    for b in (1, 9, 17, 64):
        rt.serve_batch(_padded(q, b), K, rows=b)
        rt.serve_batch(_padded(q, b), K)
    assert batched_search._cache_size() == compiled


def test_unguarded_hop_loop_reports_no_tiles(small_corpus):
    """The unfused scan computes every row: no tile counters."""
    rt = ServeRuntime.build(small_corpus.base[:200], n_shards=1,
                            params=BAMGParams(r=16, l_build=32, seed=0),
                            config=EngineConfig(l=32, max_hops=16,
                                                backend="ref"))
    q = small_corpus.queries[:4]
    _, _, status = rt.serve_batch(q, K, with_status=True, rows=2)
    assert status.tiles_run is None and status.tiles is None
    assert rt.engines[0].last_tiles is None
