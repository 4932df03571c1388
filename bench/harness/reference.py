"""The plain reference: exact brute-force k nearest neighbours under L2.

It imports nothing of the program and takes nothing the program made: only
the corpus and queries that `data` draws from the seed.  Candidates come
from one f32 distance matrix per block of queries (`||q||^2 + ||x||^2 -
2 q.x`, the product at the precision asked for); the reference re-ranks
`k + extra` of them exactly in float64 on the host, so near ties inside
the product's rounding cannot reorder its top k.

The same function at `precision="high"` (three bf16 passes) is the
control of the comparison: the nearest precision below the float32 at
`HIGHEST` that the configurations state.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BLOCK = 256          # queries per device block


def _split_bf16(a):
    hi = a.astype(jnp.bfloat16).astype(jnp.float32)
    return hi, (a - hi).astype(jnp.bfloat16).astype(jnp.float32)


def _dot_t(q, x, precision: str):
    """q @ x.T in f32 at `precision` ("highest" or "high").  XLA's CPU
    backend ignores the precision flag, so off a TPU "high" is spelled out
    as the three bf16 passes a TPU makes for it."""
    if precision == "highest":
        return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    if jax.default_backend() == "tpu":
        return jnp.dot(q, x.T, precision=jax.lax.Precision.HIGH)
    (qh, ql), (xh, xl) = _split_bf16(q), _split_bf16(x)
    exact = functools.partial(jnp.dot, precision=jax.lax.Precision.HIGHEST)
    return exact(qh, xh.T) + exact(qh, xl.T) + exact(ql, xh.T)


@functools.partial(jax.jit, static_argnames=("k", "precision"))
def _block_topk(q, x, x2, k: int, precision: str):
    d = (jnp.sum(q * q, 1, keepdims=True) + x2[None, :]
         - 2.0 * _dot_t(q, x, precision))
    neg, ids = jax.lax.top_k(-d, k)
    return ids, -neg


def _blocks(queries: np.ndarray):
    """Fixed-size blocks (the last padded by repeating row 0), so one
    program serves every block."""
    for s in range(0, len(queries), BLOCK):
        q = queries[s:s + BLOCK]
        b = len(q)
        if b < BLOCK:
            q = np.concatenate([q, np.repeat(q[:1], BLOCK - b, 0)])
        yield b, q


def brute_force(x: np.ndarray, queries: np.ndarray, k: int,
                precision: str = "highest"):
    """(ids (nq, k) int64, f32 distances (nq, k) as computed) of the k
    smallest distances at `precision`, with no exact re-rank."""
    xd = jnp.asarray(x, jnp.float32)
    x2 = jnp.sum(xd * xd, 1)
    out_i, out_d = [], []
    for b, q in _blocks(queries):
        ids, d = _block_topk(jnp.asarray(q), xd, x2, k=k, precision=precision)
        out_i.append(np.asarray(ids[:b], np.int64))
        out_d.append(np.asarray(d[:b], np.float32))
    return np.concatenate(out_i), np.concatenate(out_d)


def exact_dists(x: np.ndarray, queries: np.ndarray,
                ids: np.ndarray) -> np.ndarray:
    """(nq, c) float64 squared L2 of each query to its listed ids (ids
    outside [0, n) give NaN)."""
    out = np.full(ids.shape, np.nan)
    for s in range(0, len(queries), BLOCK):
        i = ids[s:s + BLOCK]
        ok = (i >= 0) & (i < len(x))
        v = x[np.where(ok, i, 0)].astype(np.float64)
        q = queries[s:s + BLOCK, None, :].astype(np.float64)
        out[s:s + BLOCK] = np.where(ok, ((v - q) ** 2).sum(-1), np.nan)
    return out


def exact_topk(x: np.ndarray, queries: np.ndarray, k: int, extra: int = 22):
    """(ids (nq, k) int64, float64 distances (nq, k)): the exact k nearest
    neighbours of each query, ascending."""
    cand, _ = brute_force(x, queries, k + extra, precision="highest")
    d = exact_dists(x, queries, cand)
    order = np.argsort(d, axis=1, kind="stable")[:, :k]
    return (np.take_along_axis(cand, order, 1),
            np.take_along_axis(d, order, 1))
