"""Pallas TPU kernels: PQ ADC by one-hot table lookups.

TPU adaptation: GPUs/CPUs do ADC with an in-register gather LUT; TPUs have
no fast gather, so each sub-space's code selects its table entry through a
one-hot compare against a centroid iota -- contracted on the MXU against
the (TB, K) tables when many rows share them (`_adc_kernel`), reduced on
the VPU when every row has its own table (`_adc_rowwise_kernel`, and the
fused hop loop of `repro.kernels.beam_fused`).

Both walk the M sub-spaces the same way, one 128-lane part of the codes
at a time (`subspace_groups`).  A part of at most 64 sub-spaces is scored
as one unrolled group straight from the codes.  A wider part is held
sub-space-major in a (128, S) VMEM scratch, so one sub-space is one dense
S-wide row, and a `fori_loop` scores ADC_GROUP sub-spaces an iteration,
reading their table slice from the tables ref.  Only one group's one-hots
and code columns are live at a time, so the scoring's VMEM stops growing
with M: M=240 (GIST's 4-dim sub-spaces) compiles in the default scoped
VMEM.  Every estimate is the f32 sum over m = 0 .. M-1 in order of its
table entries: each one-hot selects exactly one entry (a sum of it and
zeros), so the VPU form equals `ref.py`'s ordered sum bit for bit, in
interpret mode and on a v5e.  On the chip the MXU's f32 passes may round
`_adc_kernel`'s running sum otherwise inside a loop: in a probe at M=64,
groups of 32 changed 8 of 65,536 estimates, one unrolled group none.

Scoped VMEM, found by compiling for a described v5e under a bisected
`vmem_limit_bytes` (B=64, TB=8, TN=256, R=32, K=256, 1,024 codes; the
default limit is 16 MiB): `pq_adc_pallas` 6.67 MiB at M=64, 5.21 MiB at
128 and 7.90 MiB at 240 (the form that unrolled every sub-space: 25.2 MB
and refused at 240), `pq_adc_rowwise_pallas` 8.84, 6.35 and 10.16 MiB.
What still grows with M past 128 is the pipeline's double-buffered
(TB, M, K) tables block, 16 KB a sub-space, and the codes block: the
scoring itself holds one group.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# one-hot x table contractions must stay exact (a bf16 MXU pass would
# round the table entries), so their precision is pinned
_EXACT = jax.lax.Precision.HIGHEST
_LANES = 128
# sub-spaces scored per loop iteration of a part wider than 2 * ADC_GROUP:
# a multiple of 8, so every group starts on a whole sublane tile of the
# scratch and the tables.  A part of at most 2 * ADC_GROUP sub-spaces is
# scored as one unrolled group, with no loop (`group_size`).  Fused hop
# loop on a v5e, B=64, L = max_hops = 256: at M=64 groups of 8, 16 and 32
# cost 12%, 5% and 2% over the unrolled form; at M=240 groups of 64 need
# 25.6 MiB of scoped VMEM, groups of 32 6.95 MiB
ADC_GROUP = 32


def code_parts(m_sub: int) -> list[tuple[int, int]]:
    """(first sub-space, count) of each 128-lane part of M codes."""
    return [(m0, min(_LANES, m_sub - m0)) for m0 in range(0, m_sub, _LANES)]


def subspace_scratch(rows: int):
    """The (128, rows) int32 VMEM scratch one part of codes is held in,
    sub-space-major: row j holds sub-space m0 + j of every code row."""
    return pltpu.VMEM((_LANES, rows), jnp.int32)


def group_size(w: int) -> int:
    """Sub-spaces scored together in a part of w: all of them when w is
    at most 2 * ADC_GROUP (M=64 takes 9.10 MiB in the fused hop loop), else
    ADC_GROUP, so that the VMEM the scoring holds stops growing with M."""
    return w if w <= 2 * ADC_GROUP else ADC_GROUP


def subspace_groups(ct_ref, codes, carry, body):
    """Score one part of codes, `group_size` sub-spaces at a time, in order.

    codes (S, w) int32, w <= 128: sub-spaces m0 .. m0+w-1 of S code rows.
    `body(start, cols, carry) -> carry` runs for each group with `cols`
    (S, count) its codes and `start` its first sub-space (relative to the
    part).  A part that is one group is passed to `body` as it is.  A
    wider part is written transposed to `ct_ref` (`subspace_scratch`) and
    each group read back from it, in a `fori_loop`, and once more for the
    tail when the group size does not divide w."""
    w = codes.shape[1]
    size = group_size(w)
    if size == w:
        return body(0, codes, carry)
    ct_ref[pl.ds(0, w), :] = codes.T

    def group(start, count, carry):
        return body(start, ct_ref[pl.ds(start, count), :].T, carry)

    full = w // size
    carry = jax.lax.fori_loop(
        0, full, lambda g, c: group(pl.multiple_of(g * size, size), size, c),
        carry)
    if w % size:
        carry = group(full * size, w % size, carry)
    return carry


def adc_rowwise_part(nd, codes, ct_ref, tables_ref, m0: int):
    """nd (TB, R) f32 plus, sub-space by sub-space in order, each row's
    table entry: codes (TB*R, w) int32 are sub-spaces m0 .. m0+w-1 of the
    TB*R candidate rows (row-major, R a tile's rows per query), and
    tables_ref (TB, M, K) each query's own tables.  A (TB, R, K) one-hot
    per sub-space, reduced over K on the VPU."""
    tb, r = nd.shape
    k_cent = tables_ref.shape[2]
    kio = jax.lax.broadcasted_iota(jnp.int32, (tb, r, k_cent), 2)

    def body(start, cols, nd):
        count = cols.shape[1]
        cols = cols.reshape(tb, r, count)                    # (TB, R, count)
        tab = tables_ref[:, pl.ds(m0 + start, count), :]     # (TB, count, K)
        for i in range(count):
            hit = kio == cols[:, :, i:i + 1]
            nd = nd + jnp.sum(jnp.where(hit, tab[:, i:i + 1, :], 0.0),
                              axis=2)
        return nd

    return subspace_groups(ct_ref, codes, nd, body)


def _adc_kernel(codes_ref, tables_ref, out_ref, ct_ref, *, m_sub: int,
                k_cent: int):
    """codes (TN, M) int32 | tables (TB, M, K) f32 -> out (TB, TN) f32.

    Per sub-space a (TN, K) one-hot of its codes, contracted on the MXU
    with the (TB, K) tables of the tile."""
    tn = codes_ref.shape[0]
    tb = tables_ref.shape[0]
    col = jax.lax.broadcasted_iota(jnp.int32, (tn, k_cent), 1)
    acc = jnp.zeros((tb, tn), jnp.float32)
    for m0, w in code_parts(m_sub):

        def body(start, cols, acc, m0=m0):
            count = cols.shape[1]
            tab = tables_ref[:, pl.ds(m0 + start, count), :]  # (TB, count, K)
            for i in range(count):
                onehot = (col == cols[:, i:i + 1]).astype(jnp.float32)
                acc = acc + jax.lax.dot_general(
                    tab[:, i, :], onehot, (((1,), (1,)), ((), ())),
                    precision=_EXACT,
                    preferred_element_type=jnp.float32)       # (TB, TN)
            return acc

        acc = subspace_groups(ct_ref, codes_ref[:, m0:m0 + w], acc, body)
    out_ref[...] = acc


def _adc_rowwise_kernel(codes_ref, tables_ref, out_ref, ct_ref, *,
                        m_sub: int):
    """codes (TB, R, M) int32 | tables (TB, M, K) f32 -> out (TB, R) f32."""
    tb, r, _ = codes_ref.shape
    codes = codes_ref[...].reshape(tb * r, m_sub)
    nd = jnp.zeros((tb, r), jnp.float32)
    for m0, w in code_parts(m_sub):
        nd = adc_rowwise_part(nd, codes[:, m0:m0 + w], ct_ref, tables_ref, m0)
    out_ref[...] = nd


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def pq_adc_rowwise_pallas(tables: jnp.ndarray, cand_codes: jnp.ndarray,
                          tile_b: int = 8,
                          interpret: bool = False) -> jnp.ndarray:
    """tables (B, M, K) f32, cand_codes (B, R, M) int -> (B, R) f32.

    B must be a multiple of tile_b (ops.py pads).  One grid step scores a
    query tile's gathered candidate codes against its own tables -- the
    per-hop neighbor-scoring stage of the batched beam, kept VMEM-local
    (the one-hot form of `_adc_kernel`, reduced on the VPU because each
    row has a private table).
    """
    b, m_sub, k_cent = tables.shape
    r = cand_codes.shape[1]
    assert b % tile_b == 0, (b, tile_b)
    cand_codes = cand_codes.astype(jnp.int32)

    return pl.pallas_call(
        functools.partial(_adc_rowwise_kernel, m_sub=m_sub),
        grid=(b // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, r, m_sub), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_b, m_sub, k_cent), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r), jnp.float32),
        scratch_shapes=[subspace_scratch(tile_b * r)],
        interpret=interpret,
    )(cand_codes, tables)


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_b", "interpret"))
def pq_adc_pallas(tables: jnp.ndarray, codes: jnp.ndarray,
                  tile_n: int = 256, tile_b: int = 8,
                  interpret: bool = False) -> jnp.ndarray:
    """tables (B, M, K) f32, codes (N, M) int -> (B, N) f32 estimates.

    B and N must be multiples of the tiles (ops.py pads).
    """
    b, m_sub, k_cent = tables.shape
    n = codes.shape[0]
    assert n % tile_n == 0 and b % tile_b == 0, (n, b, tile_n, tile_b)
    codes = codes.astype(jnp.int32)

    return pl.pallas_call(
        functools.partial(_adc_kernel, m_sub=m_sub, k_cent=k_cent),
        grid=(n // tile_n, b // tile_b),
        in_specs=[
            pl.BlockSpec((tile_n, m_sub), lambda i, j: (i, 0)),
            pl.BlockSpec((tile_b, m_sub, k_cent), lambda i, j: (j, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, tile_n), lambda i, j: (j, i)),
        out_shape=jax.ShapeDtypeStruct((b, n), jnp.float32),
        scratch_shapes=[subspace_scratch(tile_n)],
        interpret=interpret,
    )(codes, tables)
