"""Distributed serving runtime: instruction-stream parity + scheduler
invariants + placement/mesh satellites.

The refactor contract (ISSUE 8): the compiled SCATTER/RUN/GATHER/MERGE
program must return *bit-identical* (ids, dists) to the pre-refactor
`ShardedFrontend` scatter-gather loop -- reimplemented here verbatim as
`_legacy_scatter_gather`, the independent oracle -- on clean fleets and
with shards down.  The scheduler must never invert deadlines when forming
micro-batches, and SLO-shrunk beams must still return valid top-k.
"""
import dataclasses
import inspect

import numpy as np
import pytest

from repro.core.engine import BAMGParams
from repro.serve import (BatchedANNEngine, BeamTier, EngineConfig,
                         Scheduler, SchedulerConfig, ServeRuntime,
                         ShardedFrontend, make_requests)
from repro.serve.frontend import _merge_topk, _pad_cols
from repro.serve.runtime import (Opcode, Request, RequestQueue,
                                 compile_program)

K = 10
_CFG = EngineConfig(l=48, max_hops=24, backend="ref")


def _legacy_scatter_gather(engines, luts, queries, k, skip=()):
    """The pre-runtime ShardedFrontend loop, kept verbatim as the oracle."""
    queries = np.atleast_2d(queries)
    b = len(queries)
    all_ids, all_d = [], []
    for s, (lut, eng) in enumerate(zip(luts, engines)):
        if s in skip:
            continue
        ks = min(k, eng.rerank_capacity)
        ids_s, d_s = eng.search_batch(queries, ks)
        if ks < k:
            ids_s = np.concatenate(
                [ids_s, np.full((b, k - ks), -1, ids_s.dtype)], axis=1)
            d_s = np.concatenate(
                [d_s, np.full((b, k - ks), np.inf, d_s.dtype)], axis=1)
        all_ids.append(lut[ids_s])
        all_d.append(d_s)
    if all_ids:
        ids = np.concatenate(all_ids, axis=1)
        d = np.concatenate(all_d, axis=1)
    else:
        ids = np.full((b, k), -1, np.int64)
        d = np.full((b, k), np.inf, np.float64)
    gd, gi = _merge_topk(d, k)
    ids = _pad_cols(ids, k, -1)
    gids = np.take_along_axis(ids, gi, axis=1)
    return np.where(np.isfinite(gd), gids, -1), gd


@pytest.fixture(scope="module")
def fleet(small_corpus):
    fe = ShardedFrontend.build(small_corpus.base, n_shards=3,
                               params=BAMGParams(r=16, l_build=32, seed=0),
                               config=_CFG)
    return small_corpus, fe


# ---------------------------------------------------------------------------
# instruction stream
# ---------------------------------------------------------------------------
def test_program_structure():
    prog = compile_program(3)
    ops = [ins.op for ins in prog]
    assert ops == [Opcode.SCATTER,
                   Opcode.RUN, Opcode.GATHER,
                   Opcode.RUN, Opcode.GATHER,
                   Opcode.RUN, Opcode.GATHER,
                   Opcode.MERGE]
    assert [ins.shard for ins in prog[1:-1]] == [0, 0, 1, 1, 2, 2]
    with pytest.raises(ValueError):
        compile_program(0)


def test_runtime_bit_identical_clean(fleet):
    ds, fe = fleet
    ids, dists = fe.search_batch(ds.queries, K)
    oids, od = _legacy_scatter_gather(fe.engines, fe._lut, ds.queries, K)
    np.testing.assert_array_equal(ids, oids)
    np.testing.assert_array_equal(dists, od)


def test_runtime_bit_identical_one_shard_down(fleet):
    """Dead shard (fault hook) -> masked RUN; answers bit-identical to the
    legacy loop skipping that shard."""
    ds, fe = fleet
    clean_ids, _ = fe.search_batch(ds.queries, K)
    fe.engines[1].inject_fault()
    try:
        ids, dists, st = fe.search_batch(ds.queries, K, with_status=True)
        assert st.degraded.all() and st.shards_down == (1,)
        fe.engines[1].heal()   # oracle must call the (healed) engine
        oids, od = _legacy_scatter_gather(fe.engines, fe._lut, ds.queries, K,
                                          skip={1})
        np.testing.assert_array_equal(ids, oids)
        np.testing.assert_array_equal(dists, od)
    finally:
        fe.engines[1].heal()
        fe.mark_up(1)
    rids, _ = fe.search_batch(ds.queries, K)
    np.testing.assert_array_equal(rids, clean_ids)


def test_masked_shard_engine_not_called(fleet):
    """A marked-down shard is skipped by instruction masking -- its engine
    is never invoked (no try/except control flow on the skip path)."""
    ds, fe = fleet
    calls = {"n": 0}
    orig = fe.engines[0].search_batch

    def counting(*a, **kw):
        calls["n"] += 1
        return orig(*a, **kw)

    # shadow via an instance attribute (deleted below -- monkeypatch would
    # restore the bound method AS an instance attribute, which a later
    # engine.replicate() would then share)
    fe.engines[0].search_batch = counting
    fe.mark_down(0)
    try:
        ids, _, st = fe.search_batch(ds.queries, K, with_status=True)
        assert calls["n"] == 0 and 0 in st.shards_down
        assert (ids >= -1).all()
    finally:
        del fe.engines[0].search_batch
        fe.mark_up(0)


def test_replica_failover_keeps_shard_up(fleet):
    """With n_replicas=2, a faulted replica fails over round-robin inside
    the RUN instruction; the shard stays up and answers stay clean."""
    ds, fe = fleet
    rt = ServeRuntime(fe.shard_vids, fe.engines,
                      host_indexes=fe.host_indexes, n_replicas=2)
    clean_ids, clean_d = rt.serve_batch(ds.queries, K)
    rt.engines[0].inject_fault()     # replica 0 of shard 0 = caller's engine
    try:
        # two batches: round-robin lands on the healthy replica first, then
        # wraps onto the faulted one, which fails over inside the RUN
        for _ in range(2):
            ids, dists, st = rt.serve_batch(ds.queries, K, with_status=True)
            assert not st.degraded.any() and st.shards_up == rt.n_shards
            np.testing.assert_array_equal(ids, clean_ids)
            np.testing.assert_array_equal(dists, clean_d)
        h = rt.health()
        assert h["shards_up"] == rt.n_shards
        assert h["per_shard"][0]["errors"] >= 1
        assert h["per_shard"][0]["last_error"].startswith("InjectedFault(")
        assert h["replicas"][0] == [False, True]
    finally:
        rt.engines[0].heal()
        rt.mark_up(0)


def _raise_not_implemented(*args, **kwargs):
    raise NotImplementedError("no lowering for this primitive")


@pytest.mark.parametrize("failure", ["raise", "lowering"])
def test_program_error_propagates_from_serve_batch(fleet, failure):
    """An engine whose program cannot be traced, lowered or compiled is a
    bug, not a dead replica: `serve_batch` raises it instead of masking
    the shard and answering -1/+inf, and the shard stays up."""
    ds, fe = fleet
    rt = ServeRuntime(fe.shard_vids, fe.engines, host_indexes=fe.host_indexes)
    eng = rt.engines[0]
    if failure == "raise":
        eng.search_batch = _raise_not_implemented
        expect = NotImplementedError
    else:   # a TPU-only Pallas program lowered for the CPU
        eng.config = dataclasses.replace(eng.config, backend="fused_pallas")
        expect = ValueError
    try:
        with pytest.raises(expect):
            rt.serve_batch(ds.queries, K)
        h = rt.health()
        assert h["shards_up"] == rt.n_shards and h["per_shard"][0]["errors"] == 0
    finally:
        eng.__dict__.pop("search_batch", None)
        eng.config = _CFG


def test_injected_fault_still_degrades(fleet):
    """`inject_fault()` raises `InjectedFault`, which the RUN survives: the
    shard masks out and the batch is answered, flagged degraded."""
    from repro.utils.faults import InjectedFault
    ds, fe = fleet
    rt = ServeRuntime(fe.shard_vids, fe.engines, host_indexes=fe.host_indexes)
    rt.engines[2].inject_fault()
    try:
        with pytest.raises(InjectedFault):
            rt.engines[2].search_batch(ds.queries, K)
        ids, _, st = rt.serve_batch(ds.queries, K, with_status=True)
        assert st.degraded.all() and st.shards_down == (2,)
        assert (ids >= 0).all()
        assert rt.health()["per_shard"][2]["last_error"].startswith(
            "InjectedFault(")
    finally:
        rt.engines[2].heal()


def test_runtime_all_shards_down(fleet):
    ds, fe = fleet
    rt = fe.runtime
    for s in range(rt.n_shards):
        rt.mark_down(s)
    try:
        ids, d, st = rt.serve_batch(ds.queries, K, with_status=True)
        assert (ids == -1).all() and np.isinf(d).all() and st.shards_up == 0
    finally:
        for s in range(rt.n_shards):
            rt.mark_up(s)


# ---------------------------------------------------------------------------
# continuous-batching scheduler
# ---------------------------------------------------------------------------
def test_queue_no_deadline_inversion():
    """EDF pop: every popped deadline precedes every remaining deadline."""
    rng = np.random.default_rng(0)
    q = RequestQueue()
    for i in range(50):
        a = float(rng.uniform(0, 1))
        q.push(Request(rid=i, query=np.zeros(4), arrival=a,
                       deadline=a + float(rng.uniform(0.01, 2.0))))
    popped = q.pop_batch(16)
    assert len(popped) == 16 and len(q) == 34
    assert max(r.deadline for r in popped) <= q.min_deadline()


def test_formation_urgent_tier_first(fleet):
    """Micro-batch formation triages by slack and runs shrunk tiers first."""
    _, fe = fleet
    sched = Scheduler(fe.runtime, SchedulerConfig(k=K, max_batch=8, slo=1.0,
                                                  shrink_slack=0.5))
    now = 0.0
    for i, dl in enumerate((0.1, 2.0, 0.2, 3.0)):   # two urgent, two relaxed
        sched.queue.push(Request(rid=i, query=np.zeros(4), arrival=0.0,
                                 deadline=dl))
    batches = sched.form_microbatches(now)
    assert [t for t, _ in batches] == [1, 0]        # shrunk tier first
    assert sorted(r.rid for r in batches[0][1]) == [0, 2]
    assert sorted(r.rid for r in batches[1][1]) == [1, 3]


def test_slo_shrunk_beam_valid_topk(fleet):
    """Near-deadline requests execute on the shrunk tier and still return
    a valid (sorted, in-corpus) top-k, flagged degraded."""
    ds, fe = fleet
    sched = Scheduler(fe.runtime,
                      SchedulerConfig(k=K, max_batch=8, slo=1e-6,
                                      tiers=(BeamTier(),
                                             BeamTier(l=16, max_hops=4))))
    # deadline == arrival: zero slack at formation, every request shrinks
    reqs = [Request(rid=i, query=q, arrival=0.0, deadline=0.0)
            for i, q in enumerate(ds.queries[:8])]
    done = sched.run(reqs)
    assert len(done) == 8
    for c in done:
        assert c.tier == 1 and c.degraded
        assert c.ids.shape == (K,) and (c.ids >= 0).all()
        assert (c.ids < len(ds.base)).all()
        assert (np.diff(c.dists) >= 0).all()


def test_low_load_matches_unscheduled(fleet):
    """With generous slack every request runs the full beam: scheduled
    answers are bit-identical to the unscheduled runtime path."""
    ds, fe = fleet
    ref_ids, ref_d = fe.runtime.serve_batch(ds.queries, K)
    sched = Scheduler(fe.runtime, SchedulerConfig(k=K, max_batch=4,
                                                  slo=1e4))
    reqs = make_requests(ds.queries, qps=50.0, slo=1e4,
                         n=len(ds.queries), seed=2)
    done = sched.run(reqs)
    assert all(c.tier == 0 and not c.degraded for c in done)
    ids = np.stack([c.ids for c in done])      # rid i served query i
    np.testing.assert_array_equal(ids, ref_ids)
    np.testing.assert_array_equal(np.stack([c.dists for c in done]), ref_d)


# ---------------------------------------------------------------------------
# satellites: mesh validation + default-instance sharing
# ---------------------------------------------------------------------------
def test_make_host_mesh_validates_axis_sizes():
    import jax

    from repro.launch.mesh import make_host_mesh
    n = len(jax.devices())
    with pytest.raises(ValueError, match="zero-sized"):
        make_host_mesh(model=n + 1)
    with pytest.raises(ValueError, match="axis sizes must be >= 1"):
        make_host_mesh(model=1, data=0)
    with pytest.raises(ValueError, match="axis sizes must be >= 1"):
        make_host_mesh(model=0)
    with pytest.raises(ValueError, match="needs"):
        make_host_mesh(model=1, data=n + 1)
    mesh = make_host_mesh()
    assert mesh.axis_names == ("data", "model")


def test_no_shared_dataclass_instance_defaults(tiny_points):
    """serve/ callables must not bake a dataclass *instance* into their
    signature (one shared object across every call)."""
    from repro.serve.deploy import BlueGreenEngine, DeploymentManager
    targets = [ShardedFrontend.build, BatchedANNEngine.__init__,
               BatchedANNEngine.from_index, DeploymentManager.validate,
               DeploymentManager.deploy, BlueGreenEngine.__init__,
               ServeRuntime.build, Scheduler.__init__]
    for fn in targets:
        for name, p in inspect.signature(fn).parameters.items():
            if p.default is inspect.Parameter.empty:
                continue
            assert not dataclasses.is_dataclass(p.default), \
                f"{fn.__qualname__}({name}=...) shares one dataclass " \
                f"instance across calls; default to None instead"
    # construct-per-call: two builds get distinct config objects
    a = ShardedFrontend.build(tiny_points, 2,
                              params=BAMGParams(r=8, l_build=16, knn_k=8))
    b = ShardedFrontend.build(tiny_points, 2,
                              params=BAMGParams(r=8, l_build=16, knn_k=8))
    assert a.engines[0].config is not b.engines[0].config


# ---------------------------------------------------------------------------
# streaming-freshness satellites (ISSUE 9): compiled-MERGE small-candidate
# regression + EDF same-deadline FIFO replay
# ---------------------------------------------------------------------------
def test_compiled_merge_fewer_candidates_than_k(fleet):
    """Regression: with all but one shard masked and a beam override that
    caps the survivor's rerank below k, the compiled MERGE sees fewer
    total candidates than k -- it must pad to k, not crash, and the tail
    must be -1/+inf."""
    ds, fe = fleet
    rt = fe.runtime
    small_l = 4
    n_valid = rt.engines[0].effective_rerank(small_l)
    assert n_valid < K                         # the premise of the test
    for s in (1, 2):
        rt.mark_down(s)
    try:
        ids, d, st = rt.serve_batch(ds.queries, K, with_status=True,
                                    l=small_l)
        assert st.shards_up == 1 and st.degraded.all()
        assert ids.shape == (len(ds.queries), K)
        assert (ids[:, :n_valid] >= 0).all()   # real results up front...
        assert (ids[:, n_valid:] == -1).all()  # ...then explicit padding
        assert np.isinf(d[:, n_valid:]).all()
        assert (np.diff(d[:, :n_valid], axis=1) >= 0).all()
        # the survivors are the true per-shard answers, globally mapped
        oids, od = fe.engines[0].search_batch(ds.queries, n_valid, l=small_l)
        np.testing.assert_array_equal(ids[:, :n_valid],
                                      fe._lut[0][np.asarray(oids)])
        np.testing.assert_array_equal(d[:, :n_valid], od)
    finally:
        rt.mark_up(1)
        rt.mark_up(2)


def test_queue_same_deadline_fifo_by_arrival():
    """Regression: requests with *equal* deadlines must dequeue in arrival
    order, even when rids are not monotone with arrival (the EDF heap
    must never fall through to comparing rids or Request objects)."""
    q = RequestQueue()
    rids = [5, 3, 9, 1, 7, 0, 8, 2]
    for i, rid in enumerate(rids):
        q.push(Request(rid=rid, query=np.zeros(4, np.float32),
                       arrival=float(i), deadline=1.0))
    out = q.pop_batch(len(rids))
    assert [r.rid for r in out] == rids        # FIFO by arrival, not by rid


def test_queue_edf_dominates_then_fifo_breaks_ties():
    """Mixed deadlines: strictly earlier deadline wins; within a deadline
    class, arrival order is preserved (stable EDF replay)."""
    q = RequestQueue()
    seq = [(9, 2.0), (4, 1.0), (7, 2.0), (1, 1.0), (8, 3.0), (0, 2.0)]
    for i, (rid, dl) in enumerate(seq):
        q.push(Request(rid=rid, query=np.zeros(2, np.float32),
                       arrival=float(i), deadline=dl))
    got = [(r.deadline, r.rid) for r in q.pop_batch(len(seq))]
    assert got == [(1.0, 4), (1.0, 1), (2.0, 9), (2.0, 7), (2.0, 0),
                   (3.0, 8)]
    assert len(q) == 0
