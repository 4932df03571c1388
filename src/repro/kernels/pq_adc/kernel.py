"""Pallas TPU kernel: PQ ADC as one-hot @ LUT matmuls on the MXU.

TPU adaptation (DESIGN.md §2): GPUs/CPUs do ADC with an in-register gather
LUT; TPUs have no fast gather, but the MXU eats (TN, K) x (K, TB) matmuls.
We loop over the M subspaces, turning each code column into a one-hot
(TN, K) tile and accumulating one-hot @ table_m^T into the (TN, TB) output.

Grid: (N // TN, B // TB).  VMEM per step ~ TN*M*4 (codes) + TB*M*K*4
(tables) + TN*K*4 (one-hot scratch) + TN*TB*4 (out): with TN=256, TB=8,
M=16, K=256 that is ~16 KB + 128 KB + 256 KB + 8 KB -- well inside VMEM.
K=256 and TN multiples of 128 keep the MXU fully aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


# one-hot x table contractions must stay exact (a bf16 MXU pass would
# round the table entries), so their precision is pinned
_EXACT = jax.lax.Precision.HIGHEST


def _adc_kernel(codes_ref, tables_ref, out_ref, *, m_sub: int, k_cent: int):
    """codes (TN, M) int32 | tables (TB, M, K) f32 -> out (TB, TN) f32."""
    tn = codes_ref.shape[0]
    tb = tables_ref.shape[0]
    codes = codes_ref[...]                      # (TN, M)
    tables = tables_ref[...]                    # (TB, M, K)
    col = jax.lax.broadcasted_iota(jnp.int32, (tn, k_cent), 1)
    acc = jnp.zeros((tb, tn), jnp.float32)
    for m in range(m_sub):                      # static: Mosaic slices
        onehot = (col == codes[:, m:m + 1]).astype(jnp.float32)  # (TN, K)
        acc = acc + jax.lax.dot_general(
            tables[:, m, :], onehot, (((1,), (1,)), ((), ())),
            precision=_EXACT, preferred_element_type=jnp.float32)  # (TB, TN)
    out_ref[...] = acc


def _adc_rowwise_kernel(codes_ref, tables_ref, out_ref, *, m_sub: int,
                        k_cent: int):
    """codes (TB, R, M) int32 | tables (TB, M, K) f32 -> out (TB, R) f32."""
    tb, r, _ = codes_ref.shape
    codes = codes_ref[...]                          # (TB, R, M)
    tables = tables_ref[...]                        # (TB, M, K)
    col = jax.lax.broadcasted_iota(jnp.int32, (tb, r, k_cent), 2)
    acc = jnp.zeros((tb, r), jnp.float32)
    for m in range(m_sub):
        onehot = (col == codes[:, :, m:m + 1]).astype(jnp.float32)  # (TB,R,K)
        acc = acc + jnp.sum(onehot * tables[:, m:m + 1, :], axis=2)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("tile_b", "interpret"))
def pq_adc_rowwise_pallas(tables: jnp.ndarray, cand_codes: jnp.ndarray,
                          tile_b: int = 8,
                          interpret: bool = False) -> jnp.ndarray:
    """tables (B, M, K) f32, cand_codes (B, R, M) int -> (B, R) f32.

    B must be a multiple of tile_b (ops.py pads).  One grid step scores a
    query tile's gathered candidate codes against its own tables -- the
    per-hop neighbor-scoring stage of the batched beam, kept VMEM-local
    (the one-hot * table form of the MXU trick in `_adc_kernel`, reduced
    on the VPU because each row has a private table).
    """
    b, m_sub, k_cent = tables.shape
    r = cand_codes.shape[1]
    assert b % tile_b == 0, (b, tile_b)
    cand_codes = cand_codes.astype(jnp.int32)

    return pl.pallas_call(
        functools.partial(_adc_rowwise_kernel, m_sub=m_sub, k_cent=k_cent),
        grid=(b // tile_b,),
        in_specs=[
            pl.BlockSpec((tile_b, r, m_sub), lambda i: (i, 0, 0)),
            pl.BlockSpec((tile_b, m_sub, k_cent), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((tile_b, r), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((b, r), jnp.float32),
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
        interpret=interpret,
    )(codes, tables)
