"""Memory-bounded distributed row gather: `out[t] = table[idx[t]]` where
`table` is row-sharded over a mesh axis and idx indexes it *globally*.

Instead of all-gathering the table (measured: 29.5 GiB x 12 live copies for
dimenet/ogb_products triplet gathers), the local shards rotate around the
axis with collective-permute; each shard picks the rows it needs from the
chunk it currently holds.  Peak extra memory = one shard chunk.

The VJP is the mirrored ring *scatter*: cotangent rows accumulate into a
rotating per-owner buffer; after P steps every owner's buffer has visited
every shard and returns home complete.  Both directions are fori_loops with
O(1) live chunks (no per-step autodiff residuals).

Call inside shard_map with `axis_name` bound.  Collective volume equals one
logical all-gather of the table per call -- the win is memory, not bytes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def _ring_perm(p: int):
    return [(j, (j + 1) % p) for j in range(p)]


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def ring_gather(table_local: jnp.ndarray, idx: jnp.ndarray,
                axis_name: str) -> jnp.ndarray:
    """table_local (R, d) = this shard's rows [me*R, (me+1)*R); idx (T,)
    global row ids (negative = padding -> zeros).  Returns (T, d)."""
    return _ring_gather_fwd_impl(table_local, idx, axis_name)


def _ring_gather_fwd_impl(table_local, idx, axis_name):
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    r, d = table_local.shape
    t = idx.shape[0]
    perm = _ring_perm(p)

    def step(i, carry):
        chunk, out = carry
        owner = (me - i) % p          # who produced the chunk we now hold
        lo = owner * r
        sel = (idx >= lo) & (idx < lo + r)
        rows = chunk[jnp.clip(idx - lo, 0, r - 1)]
        out = jnp.where(sel[:, None], rows, out)
        chunk = jax.lax.ppermute(chunk, axis_name, perm)
        return chunk, out

    out0 = jnp.zeros((t, d), table_local.dtype)
    _, out = jax.lax.fori_loop(0, p, step, (table_local, out0))
    return out


def _fwd(table_local, idx, axis_name):
    # shape/dtype ride in a zero-byte proxy (raw dtypes are not JAX types)
    proxy = jnp.zeros((table_local.shape[0], 0), table_local.dtype)
    return _ring_gather_fwd_impl(table_local, idx, axis_name), (idx, proxy)


def _bwd(axis_name, res, dout):
    idx, proxy = res
    r, dtype = proxy.shape[0], proxy.dtype
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = _ring_perm(p)
    d = dout.shape[1]

    def step(i, acc):
        # acc currently belongs to owner (me - i) % p; add our rows for it
        owner = (me - i) % p
        lo = owner * r
        sel = (idx >= lo) & (idx < lo + r)
        local = jnp.where(sel, idx - lo, r)   # r = dump row
        contrib = jax.ops.segment_sum(
            jnp.where(sel[:, None], dout, 0.0).astype(jnp.float32),
            local, num_segments=r + 1)[:r]
        acc = acc + contrib
        return jax.lax.ppermute(acc, axis_name, perm)

    acc0 = jnp.zeros((r, d), jnp.float32)
    # after p rotations each owner's accumulator is back home
    acc = jax.lax.fori_loop(0, p, step, acc0)
    return (acc.astype(dtype), None)


ring_gather.defvjp(_fwd, _bwd)


# ---------------------------------------------------------------------------
# The mirrored primitive: distributed segment-sum into a row-sharded table.
# VJP(ring_scatter_add) = ring_gather, and vice versa.
# ---------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def ring_scatter_add(values: jnp.ndarray, idx: jnp.ndarray,
                     axis_name, rows_local: int) -> jnp.ndarray:
    """out[idx[t]] += values[t] with `out` row-sharded over axis_name.

    values (T_local, d); idx (T_local,) *global* row ids (negative =
    dropped); returns this shard's (rows_local, d) slice.  Accumulation
    buffers rotate around the ring: one chunk live at a time.
    """
    return _ring_scatter_impl(values, idx, axis_name, rows_local)


def _ring_scatter_impl(values, idx, axis_name, rows_local):
    p = jax.lax.axis_size(axis_name)
    me = jax.lax.axis_index(axis_name)
    perm = _ring_perm(p)
    d = values.shape[1]

    def step(i, acc):
        owner = (me - i) % p
        lo = owner * rows_local
        sel = (idx >= lo) & (idx < lo + rows_local)
        local = jnp.where(sel, idx - lo, rows_local)  # dump row
        contrib = jax.ops.segment_sum(
            jnp.where(sel[:, None], values, 0.0).astype(jnp.float32),
            local, num_segments=rows_local + 1)[:rows_local]
        acc = acc + contrib
        return jax.lax.ppermute(acc, axis_name, perm)

    acc = jax.lax.fori_loop(0, p, step, jnp.zeros((rows_local, d), jnp.float32))
    return acc.astype(values.dtype)


def _scat_fwd(values, idx, axis_name, rows_local):
    return _ring_scatter_impl(values, idx, axis_name, rows_local), \
        (idx, jnp.zeros((0,), values.dtype))


def _scat_bwd(axis_name, rows_local, res, dout):
    idx, proxy = res
    dv = _ring_gather_fwd_impl(dout, idx, axis_name)
    return (dv.astype(proxy.dtype), None)


ring_scatter_add.defvjp(_scat_fwd, _scat_bwd)
