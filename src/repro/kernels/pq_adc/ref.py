"""Pure-jnp oracle for PQ asymmetric distance computation (ADC).

est[b, n] = sum_m tables[b, m, codes[n, m]], the f32 adds made over
m = 0 .. M-1 in order: the order the Pallas kernels accumulate in, so
they match this oracle bit for bit at any M (an XLA reduction over M
may pair the adds differently).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _sum_in_order(g: jnp.ndarray) -> jnp.ndarray:
    """g (..., M) -> (...): f32 adds over the last axis in index order."""
    acc = jnp.zeros(g.shape[:-1], g.dtype)
    for m in range(g.shape[-1]):
        acc = acc + g[..., m]
    return acc


def pq_adc_ref(tables: jnp.ndarray, codes: jnp.ndarray) -> jnp.ndarray:
    """tables (B, M, K) f32; codes (N, M) uint8/int32 -> (B, N) f32."""
    codes = codes.astype(jnp.int32)
    # gather form: for each (b, n, m) pick tables[b, m, codes[n, m]]
    g = jnp.take_along_axis(
        tables[:, None, :, :],                       # (B, 1, M, K)
        codes[None, :, :, None].astype(jnp.int32),   # (1, N, M, 1)
        axis=3,
    )  # (B, N, M, 1)
    return _sum_in_order(g[..., 0])


def pq_adc_rowwise_ref(tables: jnp.ndarray,
                       cand_codes: jnp.ndarray) -> jnp.ndarray:
    """Per-row ADC: each query scores its *own* gathered candidate codes.

    tables (B, M, K) f32; cand_codes (B, R, M) uint8/int32 -> (B, R) f32.
    The hop-loop form of ADC: the serve beam gathers each row's popped
    adjacency codes, so unlike `pq_adc_ref` there is no shared corpus
    axis.  est[b, r] = sum_m tables[b, m, cand_codes[b, r, m]].
    """
    g = jnp.take_along_axis(
        tables[:, None],                             # (B, 1, M, K)
        cand_codes[..., None].astype(jnp.int32),     # (B, R, M, 1)
        axis=3,
    )  # (B, R, M, 1)
    return _sum_in_order(g[..., 0])
