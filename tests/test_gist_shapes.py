"""GIST1M shapes on CPU: d=960 served with PQ M=240 (4-dim sub-spaces).

The ADC kernels score a group of sub-spaces at a time through a
sub-space-major scratch (`repro.kernels.pq_adc.kernel`); here, in
interpret mode, they must equal the ordered-sum oracles bit for bit at
M=240 (two 128-lane parts, each looped over in groups, the second ending
in a partial group), M=100 (one part, three groups and a partial one) and
M=20 (one part scored as one group), the streamed
hop loop must equal the resident one and `ref` at M=240, and the whole
serve path (`Scheduler` -> `ServeRuntime` -> `BatchedANNEngine`) at
d=960 must return the `ref` engine's ids.
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.kernels.beam_fused.ops import beam_hops
from repro.kernels.pq_adc.kernel import code_parts, group_size
from repro.kernels.pq_adc.ops import pq_adc, pq_adc_rowwise
from repro.kernels.pq_adc.ref import pq_adc_ref, pq_adc_rowwise_ref

K = 256


def _tables(rng, b, m):
    return jnp.asarray((rng.random((b, m, K)) * 100).astype(np.float32))


@pytest.mark.parametrize("m", (240, 20, 100))
def test_adc_kernels_bitwise_vs_ordered_ref(m):
    """Entry scoring and rowwise scoring, interpret mode, equal their
    oracles bit for bit: every estimate is the same f32 sum over
    m = 0 .. M-1 in order."""
    sizes = [group_size(w) for _, w in code_parts(m)]
    assert sizes == {240: [32, 32], 20: [20], 100: [32]}[m]
    rng = np.random.default_rng(m)
    tables = _tables(rng, 8, m)
    codes = jnp.asarray(rng.integers(0, K, (512, m)).astype(np.uint8))
    np.testing.assert_array_equal(
        np.asarray(pq_adc(tables, codes, backend="interpret")),
        np.asarray(pq_adc_ref(tables, codes)))
    cand = jnp.asarray(rng.integers(0, K, (8, 32, m)).astype(np.int32))
    np.testing.assert_array_equal(
        np.asarray(pq_adc_rowwise(tables, cand, backend="interpret")),
        np.asarray(pq_adc_rowwise_ref(tables, cand)))


def test_beam_hops_stream_equals_resident_and_ref_at_m240():
    """The streamed hop loop (code rows in two 128-lane parts) equals the
    resident one and `ref` on every output at M=240."""
    rng = np.random.default_rng(5)
    n, r, m, b, l, hops = 256, 8, 240, 8, 16, 6
    adj = rng.integers(0, n, (n, r)).astype(np.int32)
    adj[rng.random((n, r)) < 0.2] = -1
    pool_ids = np.full((b, l), -1, np.int32)
    pool_d = np.full((b, l), np.inf, np.float32)
    pool_ids[:, :3] = np.sort(rng.choice(n, (b, 3), replace=False), 1)
    pool_d[:, :3] = np.sort(rng.random((b, 3)), 1)
    args = (jnp.asarray(adj), jnp.asarray(pool_ids), jnp.asarray(pool_d),
            jnp.zeros((b, l), bool), hops)
    ops = dict(tables=_tables(rng, b, m),
               codes=jnp.asarray(rng.integers(0, K, (n, m)).astype(np.int32)))
    ref = beam_hops(*args, backend="ref", **ops)
    res = beam_hops(*args, backend="interpret", tile_b=4, n_chunk=128, **ops)
    stream = beam_hops(*args, backend="stream_interpret", tile_b=4,
                       n_chunk=128, **ops)
    assert np.asarray(ref[3]).max() == hops
    for got_s, got_r, want in zip(stream, res, ref):
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(got_r))
        np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want))


def test_gist_shaped_serve_path_matches_ref_engine():
    """d=960, PQ M=240, n=2,048 through `Scheduler` -> `ServeRuntime` ->
    `BatchedANNEngine`: the streamed fused path (interpret mode) returns
    the `ref` engine's ids, at recall@10 >= 0.90 against brute force."""
    from repro.core.engine import BAMGIndex, BAMGParams
    from repro.data.synthetic import PAPER_REGIMES, make_vector_dataset
    from repro.serve import (BatchedANNEngine, BeamTier, EngineConfig,
                             Scheduler, SchedulerConfig, ServeRuntime)
    from repro.serve.runtime.scheduler import make_requests

    regime = PAPER_REGIMES["gist-like"]
    assert regime["d"] == 960
    ds = make_vector_dataset("gist-like", n=2048, d=regime["d"], nq=64,
                             k_gt=10, n_clusters=regime["n_clusters"], seed=0)
    idx = BAMGIndex.build(ds.base, BAMGParams(
        r=16, l_build=32, knn_k=16, pq_m=240, build_backend="batched",
        seed=0))
    arrays = idx.batch_arrays(n_entry_cands=256)
    assert arrays["codes"].shape == (2048, 240)
    served = {}
    for backend in ("ref", "fused_stream_interpret"):
        engine = BatchedANNEngine(arrays, EngineConfig(
            l=64, max_hops=32, backend=backend, n_entry_cands=256))
        sched = Scheduler(ServeRuntime([np.arange(len(ds.base))], [engine]),
                          SchedulerConfig(k=10, max_batch=32, slo=1e4,
                                          tiers=(BeamTier(),)))
        done = sched.run(make_requests(ds.queries, qps=1000.0, slo=1e4,
                                       seed=1))
        assert not any(c.degraded for c in done)
        served[backend] = np.stack([c.ids for c in done])
    np.testing.assert_array_equal(served["fused_stream_interpret"],
                                  served["ref"])
    ids = served["ref"]
    recall = np.mean([len(set(a) & set(g)) / 10 for a, g in zip(ids, ds.gt)])
    assert recall >= 0.90


def test_adc_table_build_is_named_inside_the_entry_scope():
    """`batched_search` names the ADC table build `bamg.adc_tables` inside
    `bamg.entry`, beside the hop loop and the re-rank: metadata a device
    trace's scope can be read by."""
    import re
    import jax
    from repro.serve.ann_engine import batched_search
    S = jax.ShapeDtypeStruct
    n, d, m, r = 64, 16, 4, 8
    text = batched_search.lower(
        S((n, d), jnp.float32), S((n, r), jnp.int32), S((n, m), jnp.uint8),
        S((m, K, d // m), jnp.float32), S((16,), jnp.int32),
        S((16, m), jnp.uint8), S((8, d), jnp.float32), S((n,), jnp.bool_),
        k=4, l=16, max_hops=4, n_entry=2, rerank=16,
        backend="ref").compile().as_text()
    scopes = set(re.findall(r"bamg\.[a-z_]+(?:/bamg\.[a-z_]+)?", text))
    assert {"bamg.entry/bamg.adc_tables", "bamg.hop_loop",
            "bamg.rerank"} <= scopes
