"""Fused beam-hop kernel: merge equivalence, kernel parity, engine parity.

Three layers, each anchoring the next:

1. `pool_merge_ranked` (the sort-free merge the fused kernel inlines) is
   bit-identical to `pool_merge` -- swept over duplicate ids across the
   incoming chunks, all-(-1) padded rows, distance ties, and chained
   merges (the output invariant feeds the next call).
2. `beam_hops` interpret (the Pallas program on CPU) matches the jnp
   oracle `beam_hops_ref` in both scoring modes, and the ref matches the
   serve engine's unfused scan by construction (same step ops + merge).
3. The serve engine under a `fused*` backend returns bit-identical
   (ids, dists) to the unfused backend, and the fused construction
   frontier matches the width-1 batched beam.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.build.pool import pool_merge, pool_merge_ranked
from repro.core.distances import exact_knn
from repro.core.engine import BAMGIndex, BAMGParams
from repro.data.synthetic import make_vector_dataset
from repro.kernels.beam_fused import beam_hops, beam_hops_ref
from repro.serve import BatchedANNEngine, EngineConfig

RNG = np.random.default_rng(7)


# --- layer 1: pool_merge_ranked == pool_merge --------------------------------

def _sorted_pool(b, l, n_ids, n_dists=5):
    """Random pool satisfying the merge invariant: ascending (dist, id),
    unique valid ids, invalid entries exactly (-1, +inf, False).  Integer-
    quantized distances engineer ties."""
    pool_ids = np.full((b, l), -1, np.int32)
    pool_d = np.full((b, l), np.inf, np.float32)
    pool_exp = np.zeros((b, l), bool)
    nvalid = int(RNG.integers(0, l + 1))
    for bi in range(b):
        vids = RNG.choice(n_ids, size=min(nvalid, n_ids), replace=False)
        vd = RNG.integers(0, n_dists, size=len(vids)).astype(np.float32)
        o = np.lexsort((vids, vd))
        pool_ids[bi, : len(vids)] = vids[o]
        pool_d[bi, : len(vids)] = vd[o]
        pool_exp[bi, : len(vids)] = RNG.random(len(vids)) < 0.5
    return pool_ids, pool_d, pool_exp


def _assert_merges_equal(pool, cands, l):
    args = [jnp.asarray(a) for a in (*pool, *cands)]
    a = pool_merge(*args, l)
    r = pool_merge_ranked(*args, l)
    for got, want, name in zip(r, a, ("ids", "dists", "expanded")):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want),
                                      err_msg=name)
    return a


# fixed shapes keep the jit cache to a handful of entries across the sweep
@pytest.mark.parametrize("lo", (1, 5, 9, 16))
def test_pool_merge_ranked_equivalence_sweep(lo):
    b, l, r, n_ids = 3, 9, 7, 14
    for trial in range(25):
        pool = _sorted_pool(b, l, n_ids)
        cand_ids = RNG.integers(-1, n_ids, size=(b, r)).astype(np.int32)
        cand_d = np.where(cand_ids < 0, np.inf,
                          RNG.integers(0, 5, size=(b, r))).astype(np.float32)
        merged = _assert_merges_equal(pool, (cand_ids, cand_d), lo)
        # chained: the (invariant-satisfying) output is the next pool
        cand2 = RNG.integers(-1, n_ids, size=(b, r)).astype(np.int32)
        cd2 = np.where(cand2 < 0, np.inf,
                       RNG.integers(0, 5, size=(b, r))).astype(np.float32)
        _assert_merges_equal([np.asarray(m) for m in merged],
                             (cand2, cd2), lo)


def test_pool_merge_ranked_all_padded_candidates():
    """An all-(-1) candidate chunk must leave the pool bit-identical."""
    pool = _sorted_pool(4, 8, 20)
    cand_ids = np.full((4, 6), -1, np.int32)
    cand_d = np.full((4, 6), np.inf, np.float32)
    out = _assert_merges_equal(pool, (cand_ids, cand_d), 8)
    np.testing.assert_array_equal(np.asarray(out[0]), pool[0])
    np.testing.assert_array_equal(np.asarray(out[2]), pool[2])


def test_pool_merge_ranked_duplicates_across_chunks():
    """A candidate duplicating a pool id is dropped (the incumbent keeps
    its expanded flag); duplicates within the chunk collapse to one."""
    pool_ids = np.array([[3, 7, -1, -1]], np.int32)
    pool_d = np.array([[1.0, 2.0, np.inf, np.inf]], np.float32)
    pool_exp = np.array([[True, False, False, False]])
    cand_ids = np.array([[7, 5, 5, 3]], np.int32)     # 7,3 dup pool; 5 dup 5
    cand_d = np.array([[2.0, 1.5, 1.5, 1.0]], np.float32)
    out = _assert_merges_equal((pool_ids, pool_d, pool_exp),
                               (cand_ids, cand_d), 4)
    np.testing.assert_array_equal(np.asarray(out[0]), [[3, 5, 7, -1]])
    np.testing.assert_array_equal(np.asarray(out[2]),
                                  [[True, False, False, False]])


# --- layer 2: beam_hops interpret vs ref -------------------------------------

def _graph(n=300, r=8, m=4, k=16, d=6, b=5, l=12, seed=3):
    rng = np.random.default_rng(seed)
    adj = rng.integers(0, n, (n, r)).astype(np.int32)
    adj[rng.random((n, r)) < 0.2] = -1                # padded slots
    x = rng.normal(size=(n, d)).astype(np.float32)
    codes = rng.integers(0, k, (n, m)).astype(np.int32)
    tables = rng.random((b, m, k)).astype(np.float32)
    queries = rng.normal(size=(b, d)).astype(np.float32)
    seeds = np.sort(rng.choice(n, (b, 3), replace=False).astype(np.int32), 1)
    pool_ids = np.full((b, l), -1, np.int32)
    pool_d = np.full((b, l), np.inf, np.float32)
    pool_ids[:, :3] = seeds
    pool_d[:, :3] = np.sort(rng.random((b, 3)), axis=1)
    pool_exp = np.zeros((b, l), bool)
    return (jnp.asarray(adj), jnp.asarray(x), jnp.asarray(codes),
            jnp.asarray(tables), jnp.asarray(queries),
            jnp.asarray(pool_ids), jnp.asarray(pool_d),
            jnp.asarray(pool_exp))


def _assert_hops_match(ref, out):
    names = ("pool_ids", "pool_d", "pool_exp", "hops",
             "trace_ids", "trace_d", "next_id", "done")
    for got, want, name in zip(out, ref, names):
        got, want = np.asarray(got), np.asarray(want)
        if want.dtype.kind == "f":   # one-hot matmul vs gather: ulp noise
            np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5,
                                       err_msg=name)
        else:
            np.testing.assert_array_equal(got, want, err_msg=name)


def test_beam_hops_interpret_matches_ref_adc():
    adj, x, codes, tables, _, pi, pd, pe = _graph()
    ref = beam_hops_ref(adj, pi, pd, pe, 6, mode="adc",
                        tables=tables, codes=codes)
    out = beam_hops(adj, pi, pd, pe, 6, tables=tables, codes=codes,
                    backend="interpret", tile_b=4, n_chunk=128)
    _assert_hops_match(ref, out)


def test_beam_hops_interpret_matches_ref_l2():
    adj, x, codes, tables, queries, pi, pd, pe = _graph()
    n2 = jnp.sum(x * x, axis=1)
    ref = beam_hops_ref(adj, pi, pd, pe, 6, mode="l2",
                        x=x, n2=n2, queries=queries)
    out = beam_hops(adj, pi, pd, pe, 6, x=x, n2=n2, queries=queries,
                    backend="interpret", tile_b=4, n_chunk=128)
    _assert_hops_match(ref, out)


def test_beam_hops_exhausts_and_reports_done():
    """With a hop budget past exhaustion every row reports done, the next
    pick is -1, and the trace tail is (-1, +inf)."""
    adj, x, codes, tables, _, pi, pd, pe = _graph(n=40, l=40)
    out = beam_hops_ref(adj, pi, pd, pe, 60, mode="adc",
                        tables=tables, codes=codes)
    _, _, _, hops, tid, td, next_id, done = out
    assert bool(np.asarray(done).all())
    assert (np.asarray(next_id) == -1).all()
    assert (np.asarray(hops) <= 40).all()
    tail = np.asarray(tid)[np.arange(5), np.asarray(hops)]
    assert (tail == -1).all()


# --- streaming mode: HBM-resident corpus, row-DMA gathers --------------------

def test_beam_hops_stream_interpret_matches_ref_adc():
    adj, x, codes, tables, _, pi, pd, pe = _graph()
    ref = beam_hops_ref(adj, pi, pd, pe, 6, mode="adc",
                        tables=tables, codes=codes)
    out = beam_hops(adj, pi, pd, pe, 6, tables=tables, codes=codes,
                    backend="stream_interpret", tile_b=4, n_chunk=128)
    _assert_hops_match(ref, out)


def test_beam_hops_stream_interpret_matches_ref_l2():
    adj, x, codes, tables, queries, pi, pd, pe = _graph()
    n2 = jnp.sum(x * x, axis=1)
    ref = beam_hops_ref(adj, pi, pd, pe, 6, mode="l2",
                        x=x, n2=n2, queries=queries)
    out = beam_hops(adj, pi, pd, pe, 6, x=x, n2=n2, queries=queries,
                    backend="stream_interpret", tile_b=4, n_chunk=128)
    _assert_hops_match(ref, out)


@pytest.mark.parametrize("n_chunk", (64, 256))
def test_beam_hops_stream_bitwise_matches_resident(n_chunk):
    """Streaming must be *bit-identical* to the resident program at every
    slab size: both walk identical chunk contents in identical order and
    the one-hot contraction's 0.0 contributions are exact, so the DMA
    chunking can never move a single bit of ids or dists."""
    adj, x, codes, tables, queries, pi, pd, pe = _graph(n=256)
    n2 = jnp.sum(x * x, axis=1)
    kw = dict(tile_b=4)
    res = beam_hops(adj, pi, pd, pe, 6, tables=tables, codes=codes,
                    backend="interpret", n_chunk=128, **kw)
    stream = beam_hops(adj, pi, pd, pe, 6, tables=tables, codes=codes,
                       backend="stream_interpret", n_chunk=n_chunk, **kw)
    for got, want in zip(stream, res):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    res = beam_hops(adj, pi, pd, pe, 6, x=x, n2=n2, queries=queries,
                    backend="interpret", n_chunk=128, **kw)
    stream = beam_hops(adj, pi, pd, pe, 6, x=x, n2=n2, queries=queries,
                       backend="stream_interpret", n_chunk=n_chunk, **kw)
    for got, want in zip(stream, res):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend", ("interpret", "stream_interpret"))
@pytest.mark.parametrize("n_live", (1, 7, 8, 9, 57, 64))
def test_beam_hops_skips_tiles_of_padding_rows(backend, n_live):
    """With `n_live` given, a tile of rows >= n_live runs no hop loop:
    the rows of every tile that ran are bit-identical to the call without
    a count, and every row of a skipped tile holds the empty result."""
    adj, _, codes, tables, _, pi, pd, pe = _graph(b=64)
    kw = dict(tables=tables, codes=codes, backend=backend, tile_b=8,
              n_chunk=128)
    full = beam_hops(adj, pi, pd, pe, 6, **kw)
    out = beam_hops(adj, pi, pd, pe, 6, n_live=jnp.int32(n_live), **kw)
    ran = -(-n_live // 8) * 8
    empty = (-1, np.inf, False, 0, -1, np.inf, -1, True)
    for got, want, fill in zip(out, full, empty):
        got, want = np.asarray(got), np.asarray(want)
        np.testing.assert_array_equal(got[:ran], want[:ran])
        assert (got[ran:] == fill).all()


def _edge_graph(mode, d=6, n=300, r=8, m=4, k=16, b=8, l=12, seed=11):
    """A corpus of the row gather's edge cases: the last row (id n-1) in
    seeds and adjacency, rows whose adjacency is all -1, pool rows with
    no frontier from the start or after one hop, beside rows that keep
    one, in every 4-row tile; n is no multiple of the 128-row chunk, so
    `beam_hops` pads the corpus."""
    rng = np.random.default_rng(seed)
    adj = rng.integers(0, n, (n, r)).astype(np.int32)
    adj[rng.random((n, r)) < 0.2] = -1
    dead = np.arange(10, 20)                          # adjacency all -1
    adj[dead] = -1
    adj[rng.choice(n, 40, replace=False), 0] = n - 1
    adj[n - 1] = rng.integers(0, n, r)
    pool_ids = np.full((b, l), -1, np.int32)
    pool_d = np.full((b, l), np.inf, np.float32)
    seeds = {0: [n - 1, 3, 7], 2: [n - 1], 3: [dead[0]], 4: [5, n - 1],
             6: [dead[1], dead[2]], 7: [n - 2, 1]}     # rows 1, 5: empty
    for row, ids in seeds.items():
        pool_ids[row, :len(ids)] = ids
        pool_d[row, :len(ids)] = np.sort(rng.random(len(ids)))
    args = (jnp.asarray(adj), jnp.asarray(pool_ids), jnp.asarray(pool_d),
            jnp.zeros((b, l), bool))
    if mode == "adc":
        return args, dict(
            codes=jnp.asarray(rng.integers(0, k, (n, m)).astype(np.int32)),
            tables=jnp.asarray(rng.random((b, m, k)).astype(np.float32)))
    x = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    return args, dict(x=x, n2=jnp.sum(x * x, axis=1), queries=jnp.asarray(
        rng.normal(size=(b, d)).astype(np.float32)))


@pytest.mark.parametrize("mode, d", (("adc", None), ("l2", 6), ("l2", 130)))
def test_beam_hops_stream_row_gather_edge_cases_bitwise(mode, d):
    """The streamed row gather is bit-identical to the resident one-hot
    gather and to `ref` on the gather's edge cases (see `_edge_graph`);
    d=130 gives (D+1)-wide rows two 128-lane vregs wide."""
    args, ops = _edge_graph(mode, d=d)
    hops = 12
    ref = beam_hops(*args, hops, backend="ref", **ops)
    res = beam_hops(*args, hops, backend="interpret", tile_b=4, n_chunk=128,
                    **ops)
    stream = beam_hops(*args, hops, backend="stream_interpret", tile_b=4,
                       n_chunk=128, **ops)
    hops_used = np.asarray(ref[3])
    assert hops_used[1] == hops_used[5] == 0           # never a frontier
    assert hops_used[3] == 1 and hops_used.max() == hops
    assert (np.asarray(ref[4]) == args[0].shape[0] - 1).any()
    for got, want_res, want_ref in zip(stream, res, ref):
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want_res))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want_ref))


def test_beam_hops_rejects_unknown_backend():
    adj, x, codes, tables, _, pi, pd, pe = _graph()
    with pytest.raises(ValueError, match="backend"):
        beam_hops(adj, pi, pd, pe, 2, tables=tables, codes=codes,
                  backend="bogus")


def test_kernel_tiling_errors_name_offending_dims():
    """The raw kernels (callable without the ops-layer padding) must raise
    ValueErrors naming the offending dims, not bare asserts."""
    from repro.kernels.beam_fused import (beam_hops_adc_pallas,
                                          beam_hops_adc_stream)
    adj, x, codes, tables, _, pi, pd, pe = _graph()   # b=5, n=300
    f32 = lambda a: jnp.asarray(a, jnp.float32)       # noqa: E731
    args = (f32(adj), f32(codes), f32(tables), f32(pi), f32(pd), f32(pe))
    for fn in (beam_hops_adc_pallas, beam_hops_adc_stream):
        with pytest.raises(ValueError, match=r"b=5 .* tile_b=4"):
            fn(*args, 2, tile_b=4, n_chunk=300, interpret=True)
        with pytest.raises(ValueError, match=r"n=300 .* n_chunk=128"):
            fn(*args, 2, tile_b=5, n_chunk=128, interpret=True)


def test_vmem_estimator_sanity():
    from repro.kernels import beam_fused as bf
    small = bf.vmem_bytes(4096, 32, m=16)
    big = bf.vmem_bytes(1_000_000, 32, m=16)
    assert small < big
    # resident is corpus-dominated: N * (R + M) f32 is a hard lower bound
    assert big > 1_000_000 * (32 + 16) * 4
    # streaming footprint is independent of N (that is the whole point)
    s_small = bf.stream_vmem_bytes(4096, 32, m=16, n_chunk=1024)
    s_big = bf.stream_vmem_bytes(1_000_000, 32, m=16, n_chunk=1024)
    assert s_small == s_big
    assert s_big < big
    # fits_vmem is the exact <= budget comparison
    assert bf.fits_vmem(1000, 8, m=4, budget=bf.vmem_bytes(1000, 8, m=4))
    assert not bf.fits_vmem(1000, 8, m=4,
                            budget=bf.vmem_bytes(1000, 8, m=4) - 1)
    # l2 mode sizes with d=; exactly one of m=/d= is required
    assert bf.vmem_bytes(1000, 8, d=16) > bf.stream_vmem_bytes(
        1000, 8, d=16, n_chunk=128)
    with pytest.raises(ValueError, match="exactly one"):
        bf.vmem_bytes(1000, 8)
    with pytest.raises(ValueError, match="exactly one"):
        bf.vmem_bytes(1000, 8, m=4, d=16)


@pytest.mark.parametrize("dims, row_w", ((dict(m=16), 16),
                                         (dict(d=130), 131)))
def test_stream_vmem_bytes_counts_the_row_gather_scratch(dims, row_w):
    """The streaming estimate is the resident one without the corpus and
    the gather one-hot, plus the streamed kernels' VMEM scratch as
    declared (rows and ids lane-padded to 128 lanes); n_chunk no longer
    moves it."""
    from repro.kernels.beam_fused import kernel as bk
    n, r, tb, nc = 4096, 32, 8, 1024
    kw = dict(l=64, max_hops=32, tile_b=tb, **dims)
    rest = (bk.vmem_bytes(n, r, n_chunk=nc, **kw) - n * (r + row_w) * 4
            - tb * r * nc * 4)
    declared = sum(
        s.shape[0] * bk._lanes(s.shape[1]) * s.dtype.itemsize
        for s in bk._stream_scratch(tb, r, row_w)
        if str(s.memory_space) == "vmem")
    assert bk.stream_vmem_bytes(n, r, n_chunk=nc, **kw) == rest + declared
    assert bk.stream_vmem_bytes(n, r, n_chunk=64, **kw) == \
        bk.stream_vmem_bytes(n, r, n_chunk=8192, **kw)


def test_vmem_budget_env_override(monkeypatch):
    from repro.kernels import beam_fused as bf
    monkeypatch.setenv("REPRO_VMEM_BUDGET", "12345")
    assert bf.vmem_budget_bytes() == 12345
    assert not bf.fits_vmem(4096, 32, m=16)          # ~12 kB budget
    monkeypatch.delenv("REPRO_VMEM_BUDGET")
    assert bf.vmem_budget_bytes() == 16 * 2 ** 20
    assert bf.fits_vmem(4096, 32, m=16)


# --- layer 3: engine + frontier parity ---------------------------------------

@pytest.fixture(scope="module")
def built():
    ds = make_vector_dataset("fused", n=150, d=12, nq=6, k_gt=5,
                             n_clusters=3, seed=0)
    idx = BAMGIndex.build(ds.base, BAMGParams(alpha=2, beta=1.05, r=12,
                                              l_build=24, knn_k=12, seed=0))
    return ds, idx


@pytest.mark.parametrize("cfg", (dict(l=150, max_hops=150),
                                 dict(l=32, max_hops=16),
                                 dict(l=32, max_hops=16, rerank=8)))
def test_engine_fused_ref_bitwise_vs_unfused(built, cfg):
    ds, idx = built
    e0 = BatchedANNEngine.from_index(idx, EngineConfig(backend="ref", **cfg))
    e1 = BatchedANNEngine.from_index(idx,
                                     EngineConfig(backend="fused_ref", **cfg))
    i0, d0 = e0.search_batch(ds.queries, 5)
    i1, d1 = e1.search_batch(ds.queries, 5)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_engine_fused_interpret_bitwise_vs_unfused(built):
    """The Pallas program (interpret mode on CPU) drives the whole hop
    loop: identical pool -> identical exact re-rank -> identical ids."""
    ds, idx = built
    cfg = dict(l=32, max_hops=16)
    e0 = BatchedANNEngine.from_index(idx, EngineConfig(backend="ref", **cfg))
    e1 = BatchedANNEngine.from_index(
        idx, EngineConfig(backend="fused_interpret", **cfg))
    i0, d0 = e0.search_batch(ds.queries, 5)
    i1, d1 = e1.search_batch(ds.queries, 5)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_engine_fused_stream_interpret_bitwise_vs_unfused(built):
    """The HBM-streaming Pallas program (interpret mode on CPU) drives the
    whole hop loop and must land on the same pools as the unfused scan."""
    ds, idx = built
    cfg = dict(l=32, max_hops=16)
    e0 = BatchedANNEngine.from_index(idx, EngineConfig(backend="ref", **cfg))
    e1 = BatchedANNEngine.from_index(
        idx, EngineConfig(backend="fused_stream_interpret", **cfg))
    i0, d0 = e0.search_batch(ds.queries, 5)
    i1, d1 = e1.search_batch(ds.queries, 5)
    np.testing.assert_array_equal(i0, i1)
    np.testing.assert_array_equal(d0, d1)


def test_engine_fused_exhaustive_matches_host_and_brute_force(built):
    """The fused engine inherits the serve-contract of
    tests/test_serve_engine.py: exhaustive config == brute force == host."""
    from repro.core.search import search_bamg
    ds, idx = built
    n = len(ds.base)
    cands = idx.batch_arrays(n_entry_cands=256)["entry_cands"]
    eng = BatchedANNEngine.from_index(
        idx, EngineConfig(l=n, max_hops=n, n_entry=len(cands),
                          backend="fused_ref"))
    ids, _ = eng.search_batch(ds.queries, 5)
    _, gi = exact_knn(ds.base, ds.queries, 5)
    np.testing.assert_array_equal(ids, gi)
    for qi, q in enumerate(ds.queries):
        r = search_bamg(idx.store, idx.codes, idx.codec.adc_table(q), q,
                        cands.tolist(), k=5, l=n, alpha=n)
        np.testing.assert_array_equal(ids[qi], r.ids)


def test_engine_rerank_none_equals_rerank_l(built):
    """rerank=None defaults to the full pool prefix: bit-identical to an
    explicit rerank=l, on both the fused and unfused paths."""
    ds, idx = built
    for backend in ("ref", "fused_ref"):
        e0 = BatchedANNEngine.from_index(
            idx, EngineConfig(l=32, max_hops=16, rerank=None,
                              backend=backend))
        e1 = BatchedANNEngine.from_index(
            idx, EngineConfig(l=32, max_hops=16, rerank=32, backend=backend))
        i0, d0 = e0.search_batch(ds.queries, 5)
        i1, d1 = e1.search_batch(ds.queries, 5)
        np.testing.assert_array_equal(i0, i1)
        np.testing.assert_array_equal(d0, d1)
        assert e0.rerank_capacity == e1.rerank_capacity == 32


def test_frontier_fused_matches_batched_width1(built):
    """With an exhaustive pool (no evictions) the fused frontier visits
    the identical node sequence as the width-1 seen-mask beam."""
    from repro.build.frontier import frontier_pools
    from repro.core.distances import knn_graph, medoid
    ds, _ = built
    x = ds.base
    knn = knn_graph(x, 12)
    med = medoid(x)
    nodes = np.arange(len(x))
    ids_b, d_b = frontier_pools(x, knn, [med], nodes, ef=len(x), max_hops=12,
                                batch=64, width=1, backend="batched")
    ids_f, d_f = frontier_pools(x, knn, [med], nodes, ef=len(x), max_hops=12,
                                batch=64, backend="fused_ref")
    np.testing.assert_array_equal(ids_b, ids_f)
    np.testing.assert_allclose(d_b, d_f, rtol=1e-5, atol=1e-4)


def test_frontier_fused_stream_bitwise_matches_fused_interpret(built):
    """The streaming frontier runs the same Pallas hop program through the
    DMA gathers: bit-identical pools to the resident interpret frontier."""
    from repro.build.frontier import frontier_pools
    from repro.core.distances import knn_graph, medoid
    ds, _ = built
    x = ds.base
    knn = knn_graph(x, 12)
    med = medoid(x)
    nodes = np.arange(len(x))
    kw = dict(ef=24, max_hops=8, batch=64)
    ids_i, d_i = frontier_pools(x, knn, [med], nodes,
                                backend="fused_interpret", **kw)
    ids_s, d_s = frontier_pools(x, knn, [med], nodes,
                                backend="fused_stream_interpret", **kw)
    np.testing.assert_array_equal(ids_i, ids_s)
    np.testing.assert_array_equal(d_i, d_s)


def test_build_with_fused_frontier(built):
    """BuildConfig.frontier_backend plumbs through to a working build."""
    from repro.build.builder import BuildConfig, GraphBuilder
    ds, _ = built
    gb = GraphBuilder(BuildConfig(backend="batched",
                                  frontier_backend="fused_ref",
                                  batch_size=64))
    adj, entry = gb.build_nsg(ds.base, r=12, l_build=24, knn_k=12, seed=0)
    n = len(ds.base)
    assert adj.shape == (n, 12)
    assert (adj >= -1).all() and (adj < n).all()
    assert (adj[adj >= 0] != np.repeat(np.arange(n), 12)
            [adj.ravel() >= 0]).all()                  # no self loops
    with pytest.raises(ValueError, match="frontier_backend"):
        BuildConfig(frontier_backend="bogus")
