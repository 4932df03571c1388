"""Batched construction frontier: whole-batch beam candidate collection.

Vamana and NSG construction both run, for every node p, a beam search from
the medoid to collect the candidate pool that RobustPrune consumes.  The
host implementation (`repro.core.graph_build.greedy_search`) is a Python
heapq loop per node; this module runs the beam for a whole node batch at
once with only fixed-shape array ops, using the (B, L) sorted-pool pattern
of the serving engine (`repro.serve.ann_engine`) tuned for the build side:

- each hop expands the `width` best unexpanded candidates of every row at
  once (DiskANN-style beam width), cutting the sequential hop count by
  `width` for the same number of expansions;
- a (B, N) `seen` bitmask (the host's `seen` set) filters re-proposed
  nodes *before* the merge truncates -- in clustered corpora the
  neighborhoods of one hop's expansions overlap heavily, and truncating
  before deduplication would collapse the pool to a handful of distinct
  ids (build batches are a few hundred rows over a bounded corpus, so the
  mask is cheap; shard the build before it isn't);
- neighbor scoring is exact squared L2 in dot form,
  ``||w||^2 - 2 q.w + ||q||^2`` with precomputed corpus norms -- one
  batched einsum per hop (the candidate *pools* only order the beam; the
  pruner re-derives its distances, `repro.build.prune`);
- the merge is one `top_k` by distance over (B, L + width*R): candidates
  are already distinct and disjoint from the pool, so no sort-based
  dedupe is needed.

Termination differs from the host loop: the host stops when the best heap
candidate exceeds the worst of `ef` expanded results, the batch runs a
fixed hop count so every row's shape is static.  Like the host, the pool
it returns is the *expanded* (visited) set, ascending by distance.

`frontier_pools(backend="fused*")` instead runs the hops through the
fused beam-hop kernel (`repro.kernels.beam_fused`, exact-L2 mode) -- the
same VMEM-resident program the serving engine uses, at width 1 with a
`pool_merge`-invariant pool instead of the seen-mask merge.  Its per-hop
frontier trace *is* the visited set.  The pool semantics differ slightly
(the ranked merge dedupes against the live pool only, where the seen mask
dedupes against everything ever proposed), so the two backends agree
exactly when the pool is large enough that nothing useful is evicted --
the regime the 1.5x pool slack targets -- and remain recall-equivalent
otherwise.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from .chunking import map_chunks
from .pool import pool_merge

# frontier_pools backend -> the beam_hops backend the fused path pins
# (the `fused_stream*` modes run the HBM-streaming row-DMA program, for
# corpora whose resident footprint exceeds the VMEM budget)
_FUSED = {"fused": "auto", "fused_pallas": "pallas",
          "fused_interpret": "interpret", "fused_ref": "ref",
          "fused_stream": "stream", "fused_stream_interpret":
          "stream_interpret"}


@functools.partial(jax.jit, static_argnames=("ef", "max_hops", "width"))
def _frontier_batch(x, n2, adj, entries, queries,
                    ef: int, max_hops: int, width: int):
    """One jitted beam for a query batch over a padded graph.

    x (N, D) f32; n2 (N,) precomputed squared norms; adj (N, R) int32 with
    -1 pad; entries (E,) int32 shared seed ids; queries (B, D).  Returns
    (ids (B, max_hops*width) int32 with -1 pad, dists ascending): every
    node the beam *expanded*, the analog of greedy_search's visited set
    (which the host prune consumes in full, not just the best ef).
    """
    b = queries.shape[0]
    n, r = adj.shape
    q = queries.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1)                             # (B,)
    rows = jnp.arange(b)
    sentinel = jnp.iinfo(jnp.int32).max
    # beam pool slack: the host heap never forgets a pushed candidate, so
    # it can expand nodes ranked past ef once closer ones exhaust; a
    # 1.5x pool keeps those reachable instead of evicting them
    pl = ef + ef // 2

    def score(ids):
        """Exact squared L2 of each row's query to corpus ids (B, C)."""
        vecs = x[jnp.clip(ids, 0)]                          # (B, C, D)
        d = (n2[jnp.clip(ids, 0)] - 2.0 * jnp.einsum("bcd,bd->bc", vecs, q)
             + qn[:, None])
        return jnp.where(ids >= 0, jnp.maximum(d, 0.0), jnp.inf)

    def merge(pool_ids, pool_d, pool_exp, cand_ids, cand_d):
        """top-pl of pool + candidates by distance (candidates are already
        distinct and unseen, so no dedupe pass is needed)."""
        ids = jnp.concatenate([pool_ids, cand_ids], axis=1)
        d = jnp.concatenate([pool_d, cand_d], axis=1)
        exp = jnp.concatenate(
            [pool_exp, jnp.zeros(cand_ids.shape, bool)], axis=1)
        neg, o = jax.lax.top_k(-d, pl)                      # ascending d
        return (jnp.take_along_axis(ids, o, axis=1), -neg,
                jnp.take_along_axis(exp, o, axis=1))

    # --- seed the pool with the shared entries
    seen = jnp.zeros((b, n), bool).at[:, entries].set(True)
    seed_ids = jnp.broadcast_to(entries[None, :],
                                (b, entries.shape[0])).astype(jnp.int32)
    pool_ids = jnp.full((b, pl), -1, jnp.int32)
    pool_d = jnp.full((b, pl), jnp.inf, jnp.float32)
    pool_exp = jnp.zeros((b, pl), bool)
    pool_ids, pool_d, pool_exp = merge(pool_ids, pool_d, pool_exp,
                                       seed_ids, score(seed_ids))

    def step(state, _):
        pool_ids, pool_d, pool_exp, seen = state
        frontier_d = jnp.where(pool_exp | (pool_ids < 0), jnp.inf, pool_d)
        neg, jidx = jax.lax.top_k(-frontier_d, width)       # (B, W)
        has = jnp.isfinite(neg)
        v = jnp.where(has, jnp.take_along_axis(pool_ids, jidx, axis=1), 0)
        pool_exp = pool_exp.at[rows[:, None], jidx].max(has)
        nbrs = jnp.where(has[:, :, None], adj[v], -1)       # (B, W, R)
        nbrs = nbrs.reshape(b, width * r)
        # within-hop dedupe by id, then drop already-seen nodes (the pool
        # is a subset of seen, so candidates never duplicate pool entries)
        key = jnp.where(nbrs < 0, sentinel, nbrs)
        o = jnp.argsort(key, axis=1)
        key_s = jnp.take_along_axis(key, o, axis=1)
        ids_s = jnp.take_along_axis(nbrs, o, axis=1)
        dup = jnp.pad(key_s[:, 1:] == key_s[:, :-1], ((0, 0), (1, 0)))
        new = ((ids_s >= 0) & ~dup
               & ~seen[rows[:, None], jnp.clip(ids_s, 0)])
        cand = jnp.where(new, ids_s, -1)
        seen = seen.at[rows[:, None], jnp.clip(cand, 0)].max(new)
        pool_ids, pool_d, pool_exp = merge(pool_ids, pool_d, pool_exp,
                                           cand, score(cand))
        visited = (jnp.where(has, v, -1), jnp.where(has, -neg, jnp.inf))
        return (pool_ids, pool_d, pool_exp, seen), visited

    _, (vis_ids, vis_d) = jax.lax.scan(
        step, (pool_ids, pool_d, pool_exp, seen), None, length=max_hops)
    # visited (hops, B, W) -> (B, hops*W), ascending by distance: every
    # expanded node is returned even if later evicted from the beam pool
    # (greedy_search's visited dict has the same no-forgetting property)
    vis_ids = jnp.moveaxis(vis_ids, 0, 1).reshape(b, max_hops * width)
    vis_d = jnp.moveaxis(vis_d, 0, 1).reshape(b, max_hops * width)
    o = jnp.argsort(vis_d, axis=1, stable=True)
    return (jnp.take_along_axis(vis_ids, o, axis=1),
            jnp.take_along_axis(vis_d, o, axis=1))


@functools.partial(jax.jit, static_argnames=("ef", "max_hops", "backend"))
def _frontier_batch_fused(x, n2, adj, entries, queries,
                          ef: int, max_hops: int, backend: str):
    """Width-1 beam for a query batch through the fused hop kernel.

    Same operands and return contract as `_frontier_batch` with width=1:
    seed a (B, pl) `pool_merge`-invariant pool with the shared entries,
    run `max_hops` fused hops (`repro.kernels.beam_fused`, exact-L2
    scoring -- bit-identical to `_frontier_batch`'s `score`), and return
    the per-hop frontier trace stable-sorted ascending by distance.
    """
    # deferred: repro.build <-> repro.kernels.beam_fused import cycle
    # (beam_fused.ref consumes repro.build.pool)
    from repro.kernels.beam_fused.ops import beam_hops
    b = queries.shape[0]
    q = queries.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1)
    pl = ef + ef // 2                                    # same beam slack
    seed_ids = jnp.broadcast_to(entries[None, :],
                                (b, entries.shape[0])).astype(jnp.int32)
    vecs = x[jnp.clip(seed_ids, 0)]
    sd = (n2[jnp.clip(seed_ids, 0)]
          - 2.0 * jnp.einsum("bcd,bd->bc", vecs, q) + qn[:, None])
    sd = jnp.where(seed_ids >= 0, jnp.maximum(sd, 0.0), jnp.inf)
    pool_ids = jnp.full((b, pl), -1, jnp.int32)
    pool_d = jnp.full((b, pl), jnp.inf, jnp.float32)
    pool_exp = jnp.zeros((b, pl), bool)
    pool_ids, pool_d, pool_exp = pool_merge(
        pool_ids, pool_d, pool_exp, seed_ids, sd, pl)
    _, _, _, _, tid, td, _, _ = beam_hops(
        adj, pool_ids, pool_d, pool_exp, max_hops,
        x=x, n2=n2, queries=q, backend=backend)
    o = jnp.argsort(td, axis=1, stable=True)
    return (jnp.take_along_axis(tid, o, axis=1),
            jnp.take_along_axis(td, o, axis=1))


def default_hops(ef: int, width: int) -> int:
    """Hop count giving ~ef + 2*width expansions -- the host loop expands
    ~ef nodes before its bound check fires."""
    return -(-ef // width) + 2


def frontier_pools(
    x: np.ndarray,
    adj: np.ndarray,
    entries,
    node_ids: np.ndarray,
    ef: int,
    max_hops: int | None = None,
    batch: int = 256,
    width: int = 8,
    device_arrays: tuple | None = None,
    backend: str = "batched",
) -> tuple[np.ndarray, np.ndarray]:
    """Candidate pools for a set of build nodes, chunked over fixed batches.

    Runs the batched beam from `entries` toward x[node_ids] and returns
    (ids (n, max_hops*width) int32 with -1 pad, dists ascending) -- each
    row is the beam's expanded/visited set, the host prune's candidate
    source.  The last chunk is padded up to `batch` so one compilation
    serves the whole build; independent chunks are pipelined two-deep.
    `device_arrays` optionally carries preloaded `(x, n2, adj)` jnp arrays
    so repeated calls (the Vamana batch loop) skip the host->device upload
    of x.

    backend: "batched" (the seen-mask beam above) or one of
    "fused"/"fused_pallas"/"fused_interpret"/"fused_ref"/"fused_stream"/
    "fused_stream_interpret" -- the fused beam-hop kernel at width 1
    (`width` is ignored; hop count defaults to the width-1
    `default_hops`, so pass `max_hops` to bound it).  The `fused_stream*`
    modes run the HBM-streaming row-DMA program, for build
    corpora whose resident footprint exceeds the VMEM budget.
    """
    if backend != "batched" and backend not in _FUSED:
        raise ValueError(f"frontier backend must be 'batched' or one of "
                         f"{sorted(_FUSED)}, got {backend!r}")
    node_ids = np.asarray(node_ids, np.int64)
    entries = np.asarray(entries, np.int32).ravel()
    width = max(1, min(width, ef)) if backend == "batched" else 1
    if max_hops is None:
        max_hops = default_hops(ef, width)
    if device_arrays is not None:
        xj, n2, adjj = device_arrays
    else:
        xj = jnp.asarray(x, jnp.float32)
        n2 = jnp.sum(xj * xj, axis=1)
        adjj = jnp.asarray(adj, jnp.int32)
    ej = jnp.asarray(entries)
    out_w = max_hops * width
    out_ids = np.empty((len(node_ids), out_w), np.int32)
    out_d = np.empty((len(node_ids), out_w), np.float32)

    def run(s):
        chunk = node_ids[s : s + batch]
        pad = batch - len(chunk)
        qs = x[chunk]
        if pad:
            qs = np.concatenate([qs, np.zeros((pad, x.shape[1]), x.dtype)], 0)
        if backend == "batched":
            ids, d = _frontier_batch(xj, n2, adjj, ej,
                                     jnp.asarray(qs, jnp.float32),
                                     ef=ef, max_hops=max_hops, width=width)
        else:
            ids, d = _frontier_batch_fused(xj, n2, adjj, ej,
                                           jnp.asarray(qs, jnp.float32),
                                           ef=ef, max_hops=max_hops,
                                           backend=_FUSED[backend])
        out_ids[s : s + len(chunk)] = np.asarray(ids)[: len(chunk)]
        out_d[s : s + len(chunk)] = np.asarray(d)[: len(chunk)]

    map_chunks(list(range(0, len(node_ids), batch)), run)
    return out_ids, out_d
