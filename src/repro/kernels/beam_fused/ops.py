"""Public jit'd wrapper for the fused beam-hop kernel: padding + backend.

`beam_hops` runs `max_hops` fused beam hops (frontier select + gather +
score + pool merge per hop) over a seeded sorted pool and returns the
final pool plus the per-hop frontier trace, next pick, and done mask.
Two scoring modes select the operand set:

- ADC (serving): pass ``tables`` (B, M, K) and ``codes`` (N, M);
- exact L2 (construction frontier): pass ``x`` (N, D), ``n2`` (N,)
  squared norms, and ``queries`` (B, D).

backend:

- "pallas" (TPU) / "interpret" (CPU-validated kernel): the VMEM-resident
  program -- the corpus must fit the `vmem_bytes` budget;
- "stream" (TPU) / "stream_interpret" (CPU-validated): the HBM-streaming
  program -- corpus arrays stay in HBM and each hop DMAs only the rows
  it reads: TB adjacency rows, then TB*R code/vector rows
  (`stream_vmem_bytes` footprint, independent of N; `n_chunk` is only
  the row multiple the corpus is padded to).  Bit-identical to the
  resident program at every config; the oracle for both is
  `beam_hops_ref`;
- "ref": pure jnp scan, bit-identical to the unfused serve hop loop;
- "auto": on TPU, "pallas" when the resident footprint fits
  `vmem_budget_bytes()` else "stream"; "ref" elsewhere (`resolve`).

`n_live` (a traced int32, default all B rows) says that only rows
< n_live are live: the ADC kernels run no hop loop for a `tile_b` tile
of rows >= n_live and return the empty result for its rows (pool
(-1, +inf, unexpanded), 0 hops, done).  Rows < n_live are unchanged.
"ref" and the exact-L2 kernels ignore it and compute every row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernel import (beam_hops_adc_pallas, beam_hops_adc_stream,
                     beam_hops_l2_pallas, beam_hops_l2_stream, fits_vmem)
from .ref import beam_hops_ref

BACKENDS = ("auto", "pallas", "interpret", "ref", "stream",
            "stream_interpret")
TILE_B = 8       # query rows per grid step


def resolve(backend: str, n: int, r: int, *, l: int, max_hops: int,
            tile_b: int = TILE_B, n_chunk: int = 2048,
            platform: str | None = None, **dims) -> str:
    """The concrete backend `beam_hops` runs for `backend` over an (n, r)
    adjacency, with `dims` m=, k= (ADC) or d= (exact L2): "auto" is
    "pallas" on a TPU when the resident footprint fits, else "stream";
    "ref" off a TPU.  Other values pass through."""
    if backend != "auto":
        return backend
    platform = jax.default_backend() if platform is None else platform
    if platform != "tpu":
        return "ref"
    fits = fits_vmem(n, r, l=l, max_hops=max_hops, tile_b=tile_b,
                     n_chunk=min(n_chunk, max(n, 128)), **dims)
    return "pallas" if fits else "stream"


def _pad_rows(a, mult: int, fill=0):
    pad = (-a.shape[0]) % mult
    if pad == 0:
        return a
    widths = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return jnp.pad(a, widths, constant_values=fill)


@functools.partial(jax.jit, static_argnames=("max_hops", "backend", "tile_b",
                                             "n_chunk"))
def beam_hops(adj, pool_ids, pool_d, pool_exp, max_hops: int,
              tables=None, codes=None, x=None, n2=None, queries=None,
              backend: str = "auto", tile_b: int = TILE_B,
              n_chunk: int = 2048, n_live=None):
    """Fused beam-hop loop.  adj (N, R) int32 with -1 pad; the seeded pool
    (B, L) triplet must satisfy the `pool_merge` invariant (sorted by
    (dist, id), invalid = (-1, +inf, False)).

    Returns (pool_ids (B, L) int32, pool_d (B, L) f32, pool_exp (B, L)
    bool, hops (B,) int32, trace_ids (B, max_hops) int32, trace_d
    (B, max_hops) f32, next_id (B,) int32, done (B,) bool).
    """
    if backend not in BACKENDS:
        raise ValueError(f"beam_hops backend must be one of {BACKENDS}, "
                         f"got {backend!r}")
    mode = "adc" if codes is not None else "l2"
    nc = min(n_chunk, max(adj.shape[0], 128))
    dims = (dict(m=codes.shape[1], k=tables.shape[2]) if mode == "adc"
            else dict(d=x.shape[1]))
    backend = resolve(backend, *adj.shape, l=pool_ids.shape[1],
                      max_hops=max_hops, tile_b=tile_b, n_chunk=nc, **dims)
    if backend == "ref":
        return beam_hops_ref(adj, pool_ids, pool_d, pool_exp, max_hops,
                             mode=mode, tables=tables, codes=codes,
                             x=x, n2=n2, queries=queries)

    b0 = pool_ids.shape[0]
    adj_p = _pad_rows(adj.astype(jnp.float32), nc, fill=-1)
    pids = _pad_rows(pool_ids.astype(jnp.float32), tile_b, fill=-1)
    pd = _pad_rows(pool_d.astype(jnp.float32), tile_b, fill=jnp.inf)
    pexp = _pad_rows(pool_exp.astype(jnp.float32), tile_b)
    interpret = backend in ("interpret", "stream_interpret")
    stream = backend in ("stream", "stream_interpret")
    if mode == "adc":
        fn = beam_hops_adc_stream if stream else beam_hops_adc_pallas
        out = fn(adj_p, _pad_rows(codes.astype(jnp.float32), nc),
                 _pad_rows(tables.astype(jnp.float32), tile_b),
                 pids, pd, pexp, max_hops, tile_b=tile_b, n_chunk=nc,
                 interpret=interpret, n_live=n_live)
    else:
        xn = jnp.concatenate(
            [x.astype(jnp.float32), n2.astype(jnp.float32)[:, None]], axis=1)
        fn = beam_hops_l2_stream if stream else beam_hops_l2_pallas
        out = fn(adj_p, _pad_rows(xn, nc),
                 _pad_rows(queries.astype(jnp.float32), tile_b),
                 pids, pd, pexp, max_hops, tile_b=tile_b, n_chunk=nc,
                 interpret=interpret)
    ids, d, exp, hops, tid, td, nxt, done = out
    return (ids[:b0], d[:b0], exp[:b0].astype(bool), hops[:b0, 0],
            tid[:b0], td[:b0], nxt[:b0, 0], done[:b0, 0].astype(bool))
