"""Pure-jnp oracle for the fused beam-hop loop (score + merge + select).

One hop = exactly the unfused `serve.ann_engine.batched_search` step:
pop the best unexpanded pool entry per row, gather its padded adjacency
row, score the neighbors, merge into the sorted (B, L) pool, count the
hop.  Scoring comes in the two flavors the two consumers need:

- ``mode="adc"``: PQ table lookups over gathered neighbor codes, the
  serving engine's estimate (`pq_adc_rowwise_ref`: the f32 sum over the
  sub-spaces in order, as the kernels add them);
- ``mode="l2"``: exact squared L2 in dot form with precomputed corpus
  norms and a >=0 clamp, bit-identical to the construction frontier's
  ``score`` (`repro.build.frontier`), so the batched build can run the
  same hop (width=1) as the server.

The merge is `pool_merge_ranked` -- bit-identical to the serve engine's
`pool_merge` but sort-free, which is the form the Pallas kernel inlines
(and already ~2x cheaper than the concat-double-argsort under XLA CPU).
This oracle anchors *both* Pallas execution modes: the VMEM-resident
program's one-hot gather and the HBM-streaming program's row DMAs return
identical rows in identical order, so resident == streaming == ref on
every output.
Beyond the final pool, every hop emits its frontier pick (the trace the
build frontier returns as its visited set), and the loop ends with the
*next* frontier pick and a done mask, so callers chain hop programs
without re-deriving frontier state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.build.pool import pool_merge_ranked
from repro.kernels.pq_adc.ref import pq_adc_rowwise_ref


@functools.partial(jax.jit, static_argnames=("max_hops", "mode"))
def beam_hops_ref(adj, pool_ids, pool_d, pool_exp, max_hops: int,
                  mode: str = "adc", tables=None, codes=None,
                  x=None, n2=None, queries=None):
    """Run `max_hops` fused beam hops over a seeded pool.

    adj (N, R) int32 with -1 pad; pool_ids/pool_d/pool_exp (B, L) the
    seeded sorted pool (the `pool_merge` invariant: ascending (dist, id),
    invalid = (-1, +inf, False)).  mode="adc" takes tables (B, M, K) and
    codes (N, M) int32; mode="l2" takes x (N, D) f32, n2 (N,) squared
    norms and queries (B, D) f32.

    Returns (pool_ids, pool_d, pool_exp, hops (B,) int32,
    trace_ids (B, max_hops) int32, trace_d (B, max_hops) f32,
    next_id (B,) int32, done (B,) bool): the final pool, per-hop frontier
    picks (-1 / +inf where a row had no frontier left), the next
    frontier pick after the last hop, and whether the beam is exhausted.
    """
    b, l = pool_ids.shape
    rows = jnp.arange(b)
    if mode == "adc":
        codes_i = codes.astype(jnp.int32)
    else:
        q = queries.astype(jnp.float32)
        qn = jnp.sum(q * q, axis=1)

    def score(nbrs):
        if mode == "adc":
            nd = pq_adc_rowwise_ref(tables, codes_i[jnp.clip(nbrs, 0)])
            return jnp.where(nbrs >= 0, nd, jnp.inf)
        vecs = x[jnp.clip(nbrs, 0)]                       # (B, R, D)
        d = (n2[jnp.clip(nbrs, 0)]
             - 2.0 * jnp.einsum("bcd,bd->bc", vecs, q) + qn[:, None])
        return jnp.where(nbrs >= 0, jnp.maximum(d, 0.0), jnp.inf)

    def pick(pool_ids, pool_d, pool_exp):
        frontier_d = jnp.where(pool_exp | (pool_ids < 0), jnp.inf, pool_d)
        j = jnp.argmin(frontier_d, axis=1)                # (B,)
        has = jnp.isfinite(frontier_d[rows, j])
        return j, has

    def step(state, _):
        pool_ids, pool_d, pool_exp, hops = state
        j, has = pick(pool_ids, pool_d, pool_exp)
        v = jnp.where(has, pool_ids[rows, j], 0)
        vd = jnp.where(has, pool_d[rows, j], jnp.inf)
        pool_exp = pool_exp.at[rows, j].set(pool_exp[rows, j] | has)
        nbrs = jnp.where(has[:, None], adj[v], -1)        # (B, R)
        pool_ids, pool_d, pool_exp = pool_merge_ranked(
            pool_ids, pool_d, pool_exp, nbrs, score(nbrs), l)
        trace = (jnp.where(has, v, -1).astype(jnp.int32), vd)
        return (pool_ids, pool_d, pool_exp, hops + has), trace

    (pool_ids, pool_d, pool_exp, hops), (tid, td) = jax.lax.scan(
        step, (pool_ids, pool_d, pool_exp, jnp.zeros(b, jnp.int32)),
        None, length=max_hops)
    j, has = pick(pool_ids, pool_d, pool_exp)
    next_id = jnp.where(has, pool_ids[rows, j], -1).astype(jnp.int32)
    return (pool_ids, pool_d, pool_exp, hops,
            jnp.moveaxis(tid, 0, 1), jnp.moveaxis(td, 0, 1), next_id, ~has)
