"""Batched / chunked distance computation and exact kNN.

All distances are SQUARED Euclidean unless noted -- monotone with L2, so
every lune / occlusion / ordering test in the paper is unchanged, and we
avoid sqrt everywhere (matches standard ANN practice, e.g. faiss).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


@functools.partial(jax.jit, static_argnames=())
def _sq_l2(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """(na,d),(nb,d) -> (na,nb) squared L2 via the expanded form (MXU-friendly).

    The cross term runs at full f32 precision: exact ground truth must not
    ride on a single bf16 MXU pass."""
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    a2 = jnp.sum(a * a, axis=1, keepdims=True)
    b2 = jnp.sum(b * b, axis=1, keepdims=True)
    ab = jnp.matmul(a, b.T, precision=jax.lax.Precision.HIGHEST)
    d = a2 + b2.T - 2.0 * ab
    return jnp.maximum(d, 0.0)


def pairwise_sq_l2(a, b) -> np.ndarray:
    return np.asarray(_sq_l2(jnp.asarray(a), jnp.asarray(b)))


@functools.partial(jax.jit, static_argnames=("k",))
def _knn_chunk(q: jnp.ndarray, base: jnp.ndarray, k: int):
    d = _sq_l2(q, base)
    neg, idx = jax.lax.top_k(-d, k)
    return -neg, idx


def exact_knn(base: np.ndarray, queries: np.ndarray, k: int, chunk: int = 1024):
    """Exact kNN by brute force, chunked over queries. Returns (dists, ids)."""
    base_j = jnp.asarray(base, jnp.float32)
    out_d, out_i = [], []
    for s in range(0, len(queries), chunk):
        dd, ii = _knn_chunk(jnp.asarray(queries[s : s + chunk], jnp.float32), base_j, k)
        out_d.append(np.asarray(dd))
        out_i.append(np.asarray(ii))
    return np.concatenate(out_d, 0), np.concatenate(out_i, 0)


def knn_graph(x: np.ndarray, k: int, chunk: int = 1024) -> np.ndarray:
    """Exact directed kNN graph (self excluded). Returns int32 (n, k).

    Rows shorter than k (corpora with fewer than k+1 points) are padded
    with -1, the standard missing-edge sentinel -- consumers skip
    negatives.
    """
    n = x.shape[0]
    _, ids = exact_knn(x, x, min(k + 1, n), chunk=chunk)
    adj = -np.ones((n, k), np.int32)
    for i in range(n):
        row = ids[i]
        row = row[row != i][:k]
        adj[i, : len(row)] = row
    return adj


def medoid(x: np.ndarray, sample: int = 4096, seed: int = 0) -> int:
    """Approximate medoid: point closest to the dataset mean.

    For n > sample the argmin is restricted to a seeded uniform sample of
    candidate points (the mean still uses every point) -- O(sample * d)
    distance work instead of O(n * d), standard for billion-scale builds.
    `sample=None` forces the exact argmin.
    """
    mean = x.mean(axis=0, keepdims=True)
    n = len(x)
    if sample is not None and n > sample:
        cand = np.random.default_rng(seed).choice(n, size=sample,
                                                  replace=False)
        d = pairwise_sq_l2(mean, x[cand])[0]
        return int(cand[np.argmin(d)])
    d = pairwise_sq_l2(mean, x)[0]
    return int(np.argmin(d))


def recall_at_k(ids: np.ndarray, gt: np.ndarray, k: int) -> float:
    """Mean recall@k of (B, >=k) result ids against (B, >=k) ground truth.

    Padding ids (-1) never appear in ground truth, so they count as misses.
    """
    hits = sum(len(set(ids[i, :k].tolist()) & set(gt[i, :k].tolist()))
               for i in range(len(ids)))
    return hits / (len(ids) * k)
