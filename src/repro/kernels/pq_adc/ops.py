"""Public jit'd wrapper for the PQ ADC kernel: padding + backend switch."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import pq_adc_pallas, pq_adc_rowwise_pallas
from .ref import pq_adc_ref, pq_adc_rowwise_ref


def _pad_to(x: jnp.ndarray, mult: int, axis: int):
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x, n
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths), n


@functools.partial(jax.jit, static_argnames=("tile_n", "tile_b", "backend"))
def pq_adc(tables: jnp.ndarray, codes: jnp.ndarray, tile_n: int = 256,
           tile_b: int = 8, backend: str = "auto") -> jnp.ndarray:
    """ADC distance estimates.

    tables: (B, M, K) float32 -- per-query per-subspace centroid distances
    codes:  (N, M) uint8/int32 -- PQ codes of the corpus
    returns (B, N) float32

    backend: "pallas" (TPU), "interpret" (CPU-validated kernel), or "ref"
    (pure jnp); "auto" = pallas on TPU else ref.
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        return pq_adc_ref(tables, codes)
    tables_p, b0 = _pad_to(tables, tile_b, 0)
    codes_p, n0 = _pad_to(codes, tile_n, 0)
    out = pq_adc_pallas(tables_p, codes_p, tile_n=tile_n, tile_b=tile_b,
                        interpret=(backend == "interpret"))
    return out[:b0, :n0]


@functools.partial(jax.jit, static_argnames=("tile_b", "backend"))
def pq_adc_rowwise(tables: jnp.ndarray, cand_codes: jnp.ndarray,
                   tile_b: int = 8, backend: str = "auto") -> jnp.ndarray:
    """Per-row ADC estimates (the beam hop-loop form of `pq_adc`).

    tables:     (B, M, K) float32 -- per-query centroid distance tables
    cand_codes: (B, R, M) uint8/int32 -- each row's gathered neighbor codes
    returns (B, R) float32

    Same backend matrix as `pq_adc`: "pallas" (TPU), "interpret"
    (CPU-validated kernel), "ref" (pure jnp, the same ordered sum over M
    bit for bit); "auto" = pallas on TPU else ref.
    """
    if backend == "auto":
        backend = "pallas" if jax.default_backend() == "tpu" else "ref"
    if backend == "ref":
        return pq_adc_rowwise_ref(tables, cand_codes)
    tables_p, b0 = _pad_to(tables, tile_b, 0)
    codes_p, _ = _pad_to(cand_codes, tile_b, 0)
    out = pq_adc_rowwise_pallas(tables_p, codes_p, tile_b=tile_b,
                                interpret=(backend == "interpret"))
    return out[:b0]
