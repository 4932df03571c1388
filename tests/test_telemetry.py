"""The serve path's spans and counters (`repro.serve.telemetry`).

`Completion.queued` plus its batch's service is its latency; `hops` are
the hop loop's own per-row counts for the real rows of a batch, averaged
over the live shards; a runtime that reports no hops leaves them None; a
profiler trace of `Scheduler.run` nests `bamg.round` > `bamg.step` >
`bamg.device_wait`; and tracing changes no answer.
"""
from types import SimpleNamespace

import jax
import numpy as np
import pytest

from repro.core.engine import BAMGParams
from repro.serve import (BeamTier, EngineConfig, Scheduler, SchedulerConfig,
                         ServeRuntime, make_requests, telemetry)
from repro.serve.ann_engine import batched_search
from repro.serve.runtime import Request
from repro.serve.runtime import scheduler as scheduler_mod

K = 10
B = 8
# a hop budget well over what a 16-entry pool needs, so rows stop early
_CFG = EngineConfig(l=16, max_hops=64, backend="ref")


@pytest.fixture(scope="module")
def fleet(small_corpus):
    return small_corpus, ServeRuntime.build(
        small_corpus.base, n_shards=2,
        params=BAMGParams(r=16, l_build=32, seed=0), config=_CFG)


def _scheduler(runtime):
    return Scheduler(runtime, SchedulerConfig(k=K, max_batch=B, slo=10.0,
                                              tiers=(BeamTier(),)))


class _FakeClock:
    """The scheduler's clock, advanced only by the runtime below: call c
    takes 0.05 + 0.01 * c seconds."""

    def __init__(self, runtime):
        self.runtime, self.now, self.service = runtime, 0.0, []

    def perf_counter(self):
        return self.now

    def serve_batch(self, queries, k, **kw):
        out = self.runtime.serve_batch(queries, k, **kw)
        self.service.append(0.05 + 0.01 * len(self.service))
        self.now += self.service[-1]
        return out


def test_queued_plus_service_is_latency(fleet, monkeypatch):
    ds, rt = fleet
    clock = _FakeClock(rt)
    monkeypatch.setattr(scheduler_mod, "time", clock)
    reqs = make_requests(ds.queries, qps=200.0, slo=10.0, n=30, seed=1)
    done = _scheduler(clock).run(reqs, warmup=False)
    assert len(done) == 30
    assert {c.round for c in done} == set(range(len(clock.service)))
    for c in done:
        assert c.queued >= 0
        assert c.queued + clock.service[c.round] == pytest.approx(
            c.latency, abs=1e-6)
    first = min(done, key=lambda c: c.arrival)
    assert first.queued == 0.0


def test_hops_are_the_hop_loops_own_for_real_rows(fleet):
    ds, rt = fleet
    rt.mark_down(1)
    try:
        reqs = [Request(rid=i, query=q, arrival=0.0, deadline=10.0)
                for i, q in enumerate(ds.queries[:5])]
        done = _scheduler(rt).run(reqs, warmup=False)
    finally:
        rt.mark_up(1)
    assert len(done) == 5 and {c.round for c in done} == {0}
    eng = rt.engines[0]
    assert eng.last_hops.shape == (B,)        # padded rows ran, too
    q = np.concatenate([ds.queries[:5], np.tile(ds.queries[:1], (B - 5, 1))])
    *_, hops = batched_search(
        eng.x, eng.adj, eng.codes, eng.codebooks, eng.entry_cands,
        eng.entry_codes, q, eng.tomb, k=K, l=eng._l,
        max_hops=_CFG.max_hops, n_entry=eng._n_entry, rerank=eng._rerank,
        backend=_CFG.backend)
    hops = np.asarray(hops)
    np.testing.assert_array_equal([c.hops for c in done], hops[:5])
    for c in done:
        assert c.hops_run == _CFG.max_hops
        assert 0 <= c.hops <= c.hops_run
    assert min(c.hops for c in done) < _CFG.max_hops


def test_hops_mean_over_live_shards(fleet):
    ds, rt = fleet
    q = ds.queries[:B]
    _, _, both = rt.serve_batch(q, K, with_status=True)
    e0, e1 = rt.engines
    np.testing.assert_allclose(both.hops, (e0.last_hops + e1.last_hops) / 2)
    assert both.hops_run == _CFG.max_hops
    rt.mark_down(1)
    try:
        _, _, one = rt.serve_batch(q, K, with_status=True)
    finally:
        rt.mark_up(1)
    assert one.shards_down == (1,)
    np.testing.assert_array_equal(one.hops, e0.last_hops)


class _NoHopsRuntime:
    """A duck-typed runtime whose status has only `degraded`."""

    def serve_batch(self, queries, k, with_status=False, **kw):
        ids = np.zeros((len(queries), k), np.int64)
        dists = np.zeros((len(queries), k), np.float32)
        status = SimpleNamespace(degraded=np.zeros(len(queries), bool))
        return (ids, dists, status) if with_status else (ids, dists)


def test_status_without_hops_gives_none(small_corpus):
    reqs = make_requests(small_corpus.queries, qps=100.0, slo=10.0, seed=2)
    done = _scheduler(_NoHopsRuntime()).run(reqs)
    assert len(done) == len(reqs)
    assert all(c.hops is None and c.hops_run is None for c in done)
    assert all(c.queued is not None and c.round is not None for c in done)


def _host_spans(trace_dir):
    from jax.profiler import ProfileData
    (path,) = trace_dir.rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return [(e.name, e.start_ns, e.end_ns, dict(e.stats))
            for p in pd.planes if not p.name.startswith("/device:")
            for line in p.lines for e in line.events
            if e.name.startswith("bamg.")]


def _inside(inner, outers):
    return any(o[1] <= inner[1] and inner[2] <= o[2] for o in outers)


def test_profiler_trace_nests_the_spans(fleet, tmp_path):
    ds, rt = fleet
    sched = _scheduler(rt)
    reqs = make_requests(ds.queries, qps=100.0, slo=10.0, n=24, seed=3)
    sched.warmup(ds.queries.shape[1])
    jax.profiler.start_trace(str(tmp_path))
    try:
        done = sched.run(reqs, warmup=False)
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(tmp_path)
    by = {name: [s for s in spans if s[0] == name]
          for name in (telemetry.ROUND, telemetry.STEP,
                       telemetry.DEVICE_WAIT, telemetry.FETCH)}
    rounds = {c.round for c in done}
    assert sorted(s[3]["round"] for s in by[telemetry.ROUND]) == sorted(
        rounds)
    assert len(by[telemetry.STEP]) == len(rounds)    # one tier: one call
    # two live shards: one device wait and one fetch per shard and step
    assert len(by[telemetry.DEVICE_WAIT]) == len(by[telemetry.FETCH]) == \
        2 * len(rounds)
    assert all(_inside(s, by[telemetry.ROUND]) for s in by[telemetry.STEP])
    for name in (telemetry.DEVICE_WAIT, telemetry.FETCH):
        assert all(_inside(s, by[telemetry.STEP]) for s in by[name])


def test_tracing_changes_no_answer(fleet, tmp_path):
    ds, rt = fleet
    plain = rt.serve_batch(ds.queries, K)
    jax.profiler.start_trace(str(tmp_path))
    try:
        traced = rt.serve_batch(ds.queries, K)
    finally:
        jax.profiler.stop_trace()
    for a, b in zip(plain, traced):
        np.testing.assert_array_equal(a, b)
        assert a.dtype == b.dtype
