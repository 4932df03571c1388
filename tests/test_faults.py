"""Fault injection, resilient I/O, degraded serving, blue/green deploy.

Locks down the PR's three contracts:

1. Determinism -- a `FaultPlan` is a pure function of (seed, kind, block,
   attempt): the schedule is bit-reproducible and independent of the order
   reads are issued in.
2. Accounting purity -- with a zero-rate plan (even with retry/hedge/
   timeout configured) every engine is bit-identical to no plan at all:
   same ids, dists, NIO, cache stats; zero resilience counters.
3. Degrade, never crash -- transient errors are retried to success
   (>=95%% non-degraded at the default budget under 1%% read errors),
   dead blocks/shards produce partial answers with the `degraded` flag,
   and blue/green promotion+rollback serves correct top-k throughout.
"""
import os
import sys
import tempfile

import numpy as np
import pytest

sys.path.insert(0, "src")

from repro.core.distances import recall_at_k
from repro.core.engine import (BAMGIndex, BAMGParams, DiskANNIndex,
                               DiskANNParams, StarlingIndex, StarlingParams)
from repro.serve import BlueGreenEngine, DeploymentManager
from repro.serve.ann_engine import BatchedANNEngine, EngineConfig
from repro.serve.frontend import ShardedFrontend, _merge_topk
from repro.utils.faults import (FaultPlan, FaultSpec, IntegrityError,
                                RetryPolicy, SimulatedFailure,
                                corrupt_payload, payload_checksum)

K, L = 10, 48
_CFG = EngineConfig(l=32, max_hops=16, backend="ref")


@pytest.fixture(scope="module")
def bamg(small_corpus):
    return BAMGIndex.build(small_corpus.base, BAMGParams(seed=0))


@pytest.fixture(scope="module")
def diskann(small_corpus):
    return DiskANNIndex.build(small_corpus.base, DiskANNParams(seed=0))


@pytest.fixture(scope="module")
def starling(small_corpus):
    return StarlingIndex.build(small_corpus.base, StarlingParams(seed=0))


def _batch(idx, ds, **kw):
    return idx.search_batch(ds.queries, k=K, l=L, gt=ds.gt, **kw)


def _ids(idx, ds):
    return np.stack([np.pad(r.ids[:K], (0, K - min(K, len(r.ids))),
                            constant_values=-1)
                     for r in (idx.search(q, k=K, l=L) for q in ds.queries)])


# ---------------------------------------------------------------------------
# 1. plan determinism
# ---------------------------------------------------------------------------
def test_fault_plan_reproducible_and_order_independent():
    spec = FaultSpec(read_error_rate=0.1, dead_rate=0.05, corrupt_rate=0.05,
                     spike_rate=0.1)
    keys = [(k, b, a) for k in ("graph", "vector")
            for b in range(64) for a in range(3)]
    p1, p2 = FaultPlan(spec, seed=11), FaultPlan(spec, seed=11)
    draws1 = [p1.outcome(*kk) for kk in keys]
    # same seed, reversed issue order -> identical schedule
    draws2 = list(reversed([p2.outcome(*kk) for kk in reversed(keys)]))
    assert draws1 == draws2
    assert [p1.dead(k, b) for k, b, _ in keys] == \
           [p2.dead(k, b) for k, b, _ in keys]
    # a different seed gives a different schedule
    p3 = FaultPlan(spec, seed=12)
    assert draws1 != [p3.outcome(*kk) for kk in keys]
    # zero-rate spec never draws anything
    p0 = FaultPlan(FaultSpec(), seed=11)
    assert not any(o.error or o.persistent or o.corrupt or o.spike_us
                   for o in (p0.outcome(*kk) for kk in keys))
    assert not FaultSpec().any_io


def test_checksum_roundtrip_and_corruption():
    rng = np.random.default_rng(0)
    payload = rng.standard_normal(32).astype(np.float32)
    c0 = payload_checksum(payload)
    assert c0 == payload_checksum(payload.copy())        # content-addressed
    bad = corrupt_payload(payload, salt=3)
    assert payload_checksum(bad) != c0                   # flips are visible
    assert c0 == payload_checksum(payload)               # original untouched
    bad2 = corrupt_payload(payload, salt=3)
    np.testing.assert_array_equal(bad, bad2)             # deterministic salt
    assert payload_checksum(None) == 0


# ---------------------------------------------------------------------------
# 2. zero-fault accounting purity
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("which", ["bamg", "diskann", "starling"])
def test_zero_rate_plan_bit_identical(which, small_corpus, request):
    idx = request.getfixturevalue(which)
    ds = small_corpus
    idx.configure_io(faults=None, retry=None, timeout_us=None, hedge_us=None)
    clean, clean_ids = _batch(idx, ds), _ids(idx, ds)
    # zero-rate plan WITH retry/hedge/timeout armed: nothing may change
    idx.configure_io(faults=FaultSpec(), retry=RetryPolicy(budget=4),
                     timeout_us=10_000.0, hedge_us=200.0)
    z, z_ids = _batch(idx, ds), _ids(idx, ds)
    assert (z.recall, z.mean_nio, z.cache_hit_rate) == \
           (clean.recall, clean.mean_nio, clean.cache_hit_rate)
    assert (z.mean_service_us, z.mean_serial_us) == \
           (clean.mean_service_us, clean.mean_serial_us)
    assert z.mean_retries == 0 and z.mean_hedges == 0
    assert z.degraded_fraction == 0 and z.mean_failed_reads == 0
    np.testing.assert_array_equal(z_ids, clean_ids)
    idx.configure_io(faults=None, retry=None, timeout_us=None, hedge_us=None)


# ---------------------------------------------------------------------------
# 3. resilient reads / degraded mode
# ---------------------------------------------------------------------------
def test_transient_errors_retried_to_identical_answers(bamg, small_corpus):
    ds = small_corpus
    bamg.configure_io(faults=None, retry=None, timeout_us=None, hedge_us=None)
    clean, clean_ids = _batch(bamg, ds), _ids(bamg, ds)
    # acceptance plan: 1% read errors, default retry budget
    bamg.configure_io(faults=FaultSpec(read_error_rate=0.01), fault_seed=5)
    a = _batch(bamg, ds)
    assert a.degraded_fraction <= 0.05         # >=95% non-degraded, no crash
    assert a.recall == clean.recall
    # hotter plan so the retry machinery demonstrably fires (error draws are
    # per distinct (block, attempt), so 1% can legitimately draw nothing on
    # a small corpus)
    bamg.configure_io(faults=FaultSpec(read_error_rate=0.05), fault_seed=5)
    f, f_ids = _batch(bamg, ds), _ids(bamg, ds)
    assert f.degraded_fraction <= 0.05
    assert f.mean_retries > 0                  # the errors really fired
    assert f.mean_nio == clean.mean_nio        # NIO counts deliveries only
    assert f.recall == clean.recall
    np.testing.assert_array_equal(f_ids, clean_ids)
    assert f.mean_service_us > clean.mean_service_us   # retries cost time
    bamg.configure_io(faults=None)


def test_corruption_detected_and_reread(bamg, small_corpus):
    ds = small_corpus
    bamg.configure_io(faults=FaultSpec(corrupt_rate=0.05), fault_seed=9)
    r = bamg.search_batch(ds.queries, k=K, l=L, gt=ds.gt)
    total_csf = sum(bamg.search(q, k=K, l=L).checksum_failures
                    for q in ds.queries)
    assert total_csf > 0                       # torn payloads were caught
    assert r.degraded_fraction <= 0.05         # and re-read to success
    bamg.configure_io(faults=None)


def test_dead_blocks_degrade_not_crash(bamg, small_corpus):
    ds = small_corpus
    bamg.configure_io(faults=FaultSpec(dead_rate=0.05, read_error_rate=0.02),
                      fault_seed=1, retry=RetryPolicy(budget=2))
    r = _batch(bamg, ds)
    assert r.mean_failed_reads > 0             # some blocks were lost
    assert r.degraded_fraction > 0             # and flagged as degraded
    assert r.recall > 0.5                      # but answers remain useful
    res = bamg.search(ds.queries[0], k=K, l=L)
    assert res.degraded == (res.failed_reads > 0)
    bamg.configure_io(faults=None, retry=None)


def test_hedge_and_timeout_counters(bamg, small_corpus):
    ds = small_corpus
    # heavy spikes + an aggressive hedge: hedges must fire and win sometimes
    bamg.configure_io(faults=FaultSpec(spike_rate=0.3, spike_us=5000.0),
                      fault_seed=2, hedge_us=100.0)
    r = _batch(bamg, ds)
    assert r.mean_hedges > 0
    assert r.degraded_fraction == 0            # hedging never loses data
    # tight timeout turns spikes into retried attempts instead
    bamg.configure_io(faults=FaultSpec(spike_rate=0.3, spike_us=5000.0),
                      fault_seed=2, hedge_us=None, timeout_us=500.0)
    t = _batch(bamg, ds)
    assert t.mean_retries > 0
    bamg.configure_io(faults=None, timeout_us=None)


def test_service_time_invariant_holds_under_faults(bamg, small_corpus):
    ds = small_corpus
    bamg.configure_io(faults=FaultSpec(read_error_rate=0.05, spike_rate=0.2),
                      fault_seed=4, qd=8, batch_io=True)
    for q in ds.queries:
        r = bamg.search(q, k=K, l=L)
        assert r.service_us <= r.serial_us + 1e-6
    bamg.configure_io(faults=None, qd=1, batch_io=False)


def test_device_checksums_verify_both_layouts(bamg, diskann):
    gdev = bamg.store.graph_dev
    for b in range(min(8, len(gdev))):
        assert gdev.verify(b)
        assert not gdev.verify(b, gdev.attempt_payload(b, corrupt=True,
                                                       salt=1))
    vdev = bamg.store.vector_dev
    for b in range(min(8, len(vdev))):
        assert vdev.verify(b)
        assert not vdev.verify(b, vdev.attempt_payload(b, corrupt=True))
    cdev = diskann.store.device
    for b in range(min(8, len(cdev))):
        assert cdev.verify(b)
        assert not cdev.verify(b, cdev.attempt_payload(b, corrupt=True))


# ---------------------------------------------------------------------------
# 4. sharded front-end: dead shards + small-shard merge regression
# ---------------------------------------------------------------------------
def test_merge_topk_fewer_candidates_than_k():
    d = np.array([[3.0, 1.0], [np.inf, 2.0]])
    gd, gi = _merge_topk(d, 5)                 # 2 columns, k=5: must not crash
    assert gd.shape == (2, 5)
    assert gd[0, 0] == 1.0 and gd[0, 1] == 3.0 and np.isinf(gd[0, 2:]).all()
    assert gd[1, 0] == 2.0 and np.isinf(gd[1, 1:]).all()


@pytest.fixture(scope="module")
def frontend(small_corpus):
    return ShardedFrontend.build(small_corpus.base, n_shards=3,
                                 params=BAMGParams(seed=0), config=_CFG)


def test_frontend_small_shards_padded(small_corpus):
    """Every shard smaller than k: merge must still return exact-ish top-k."""
    rng = np.random.default_rng(3)
    x = rng.standard_normal((20, 8)).astype(np.float32)
    q = rng.standard_normal((4, 8)).astype(np.float32)
    fe = ShardedFrontend.build(x, n_shards=4,
                               config=EngineConfig(l=5, max_hops=8,
                                                   backend="ref"))
    k = 12                                     # > any shard's 5 vectors
    ids, d = fe.search_batch(q, k)
    assert ids.shape == (4, k) and d.shape == (4, k)
    from repro.core.distances import exact_knn
    gt = exact_knn(x, q, k)[1]
    assert recall_at_k(ids, gt, k) >= 0.9
    order = np.argsort(d, axis=1, kind="stable")
    np.testing.assert_array_equal(order, np.tile(np.arange(k), (4, 1)))


def test_frontend_dead_shard_skip_and_recover(frontend, small_corpus):
    ds = small_corpus
    clean_ids, _ = frontend.search_batch(ds.queries, K)
    clean_rec = recall_at_k(clean_ids, ds.gt, K)
    frontend.engines[1].inject_fault()
    ids, d, st = frontend.search_batch(ds.queries, K, with_status=True)
    assert st.degraded.all() and st.shards_down == (1,)
    assert frontend.health()["shards_down"] == [1]
    assert frontend.health()["per_shard"][1]["errors"] == 1
    assert frontend.health()["per_shard"][1]["last_error"].startswith(
        "InjectedFault(")
    deg_rec = recall_at_k(ids, ds.gt, K)
    assert 0 < deg_rec < clean_rec             # partial but useful
    # the marked-down shard is skipped without another engine call
    ids2, _, st2 = frontend.search_batch(ds.queries, K, with_status=True)
    assert frontend.health()["per_shard"][1]["errors"] == 1
    np.testing.assert_array_equal(ids, ids2)
    # repair: heal + mark_up restores bit-identical clean serving
    frontend.engines[1].heal()
    frontend.mark_up(1)
    ids3, _, st3 = frontend.search_batch(ds.queries, K, with_status=True)
    assert not st3.degraded.any()
    np.testing.assert_array_equal(ids3, clean_ids)


def test_frontend_all_shards_down(frontend, small_corpus):
    for s in range(frontend.n_shards):
        frontend.mark_down(s)
    ids, d, st = frontend.search_batch(small_corpus.queries, K,
                                       with_status=True)
    assert (ids == -1).all() and np.isinf(d).all() and st.shards_up == 0
    for s in range(frontend.n_shards):
        frontend.mark_up(s)


# ---------------------------------------------------------------------------
# 5. blue/green deployment
# ---------------------------------------------------------------------------
def test_blue_green_lifecycle(small_corpus, tmp_path):
    ds = small_corpus
    dm = DeploymentManager(str(tmp_path))
    assert dm.active() is None and dm.builds() == []
    man = dm.deploy(ds.base, "v1", ds.queries, ds.gt,
                    params=BAMGParams(seed=0), k=K, min_recall=0.5,
                    config=_CFG)
    assert dm.active() == "v1" and man.meta["validated_recall"] >= 0.5
    assert man.n == len(ds.base) and man.d == ds.base.shape[1]
    bg = BlueGreenEngine(dm, _CFG)
    ids1, d1 = bg.search_batch(ds.queries, K)
    rec1 = recall_at_k(ids1, ds.gt, K)
    assert rec1 >= 0.5
    # green build promoted; blue serves identically until refresh
    dm.deploy(ds.base, "v2", ds.queries, ds.gt, params=BAMGParams(seed=1),
              k=K, min_recall=0.5, config=_CFG)
    pre, _ = bg.search_batch(ds.queries, K)
    np.testing.assert_array_equal(pre, ids1)
    assert bg.refresh() and bg.build_id == "v2"
    assert not bg.refresh()                    # idempotent
    ids2, _ = bg.search_batch(ds.queries, K)
    assert recall_at_k(ids2, ds.gt, K) >= 0.5  # correct top-k after the swap
    # rollback re-activates v1 and serving returns bit-identical
    assert dm.rollback() == "v1"
    assert bg.refresh() and bg.build_id == "v1"
    back, _ = bg.search_batch(ds.queries, K)
    np.testing.assert_array_equal(back, ids1)
    assert dm.history()[-1] == "v1"


def test_deploy_tamper_detected(small_corpus, tmp_path):
    ds = small_corpus
    dm = DeploymentManager(str(tmp_path))
    idx = BAMGIndex.build(ds.base, BAMGParams(seed=0))
    dm.publish(idx, "b1")
    dm.verify("b1")                            # clean round-trip
    art = os.path.join(str(tmp_path), "builds", "b1", "index.npz")
    with open(art, "r+b") as f:
        f.seek(64)
        f.write(b"\x00\x01\x02\x03")
    with pytest.raises(IntegrityError):
        dm.verify("b1")
    with pytest.raises(IntegrityError):
        dm.load("b1")                          # corrupt build is unloadable


def test_deploy_failed_validation_keeps_active(small_corpus, tmp_path):
    ds = small_corpus
    dm = DeploymentManager(str(tmp_path))
    dm.deploy(ds.base, "good", ds.queries, ds.gt, params=BAMGParams(seed=0),
              k=K, min_recall=0.5, config=_CFG)
    with pytest.raises(ValueError, match="failed validation"):
        dm.deploy(ds.base, "bad", ds.queries, ds.gt,
                  params=BAMGParams(seed=1), k=K, min_recall=1.01,
                  config=_CFG)
    assert dm.active() == "good"               # bad deploy degraded nothing
    assert "bad" in dm.builds()                # left published for forensics
    dm.prune(keep=1)
    assert dm.builds() == ["good"]             # prune never drops the active


# ---------------------------------------------------------------------------
# 6. unified training-failure taxonomy
# ---------------------------------------------------------------------------
def test_ft_shares_fault_taxonomy(tmp_path):
    from repro.train.ft import (FTConfig, InjectedFault, run_with_recovery)
    from repro.train.ft import SimulatedFailure as FtFailure
    assert FtFailure is SimulatedFailure
    assert issubclass(FtFailure, InjectedFault)

    def init_fn():
        return {"step": np.asarray(0), "w": np.zeros(3, np.float32)}

    def step_fn(state, batch):
        return ({"step": state["step"] + 1, "w": state["w"] + batch},
                {"loss": float(batch.sum())})

    def batch_fn(s):
        return np.full(3, float(s), np.float32)

    # a plan whose transient step failures clear on the restart attempt
    plan = next(p for p in (FaultPlan(FaultSpec(step_fail_rate=0.15), seed=s)
                            for s in range(300))
                if any(p.fail_step(i, 0) for i in range(1, 16))
                and not any(p.fail_step(i, 1) for i in range(1, 16)))
    ft = FTConfig(ckpt_dir=str(tmp_path / "a"), ckpt_every=4,
                  async_save=False)
    state, _, attempts = run_with_recovery(init_fn, step_fn, batch_fn, 15,
                                           ft, fault_plan=plan)
    assert attempts >= 1 and int(state["step"]) == 15
    ft2 = FTConfig(ckpt_dir=str(tmp_path / "b"), ckpt_every=4,
                   async_save=False)
    ref, _, a0 = run_with_recovery(init_fn, step_fn, batch_fn, 15, ft2)
    assert a0 == 0
    np.testing.assert_array_equal(ref["w"], state["w"])  # restart-equivalent


# ---------------------------------------------------------------------------
# 7. streaming-freshness satellites (ISSUE 9): tombstones under faults +
#    prune protecting ACTIVE and the rollback target
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("which", ["bamg", "diskann", "starling"])
def test_deleted_ids_never_surface_under_faults(which, small_corpus, request):
    """Tombstone masking composes with fault injection: a deleted id must
    not surface even when its (or any) block READ_FAILEDs and the
    degraded skip-and-continue path activates -- on all three engines."""
    idx = request.getfixturevalue(which)
    ds = small_corpus
    # tombstone the exact top-1 of every query: the ids most likely to leak
    dead = set(ds.gt[:, 0].astype(int).tolist())
    idx.configure_io(faults=FaultSpec(dead_rate=0.15, read_error_rate=0.05),
                     fault_seed=3)
    try:
        n_degraded = 0
        for q in ds.queries:
            r = idx.search(q, k=K, l=L, exclude=dead)
            assert not (set(r.ids.tolist()) & dead)
            n_degraded += bool(r.degraded)
        assert n_degraded > 0       # skip-and-continue actually activated
    finally:
        idx.configure_io(faults=None, retry=None)
    # clean path: the mask alone never degrades anything
    r = idx.search(ds.queries[0], k=K, l=L, exclude=dead)
    assert not r.degraded and not (set(r.ids.tolist()) & dead)


def test_prune_protects_active_and_rollback_target(small_corpus, tmp_path):
    """Regression: aggressive prune (keep=0) must never delete the build
    being served or strand rollback()."""
    ds = small_corpus
    dm = DeploymentManager(str(tmp_path))
    idx = BAMGIndex.build(ds.base, BAMGParams(seed=0))
    for b in ("b1", "b2", "b3", "b4"):
        dm.publish(idx, b)
        dm.promote(b)
    dm.promote("b2")                # re-activate an *old* build
    removed = dm.prune(keep=0)      # as aggressive as it gets
    assert set(removed) == {"b1", "b3"}
    assert dm.active() == "b2"
    assert set(dm.builds()) == {"b2", "b4"}    # ACTIVE + rollback target
    dm.verify("b2")                            # ACTIVE still verifies
    assert dm.rollback() == "b4"               # rollback still succeeds
    dm.verify("b4")
    assert dm.active() == "b4" and "b2" in dm.builds()
