"""Fixed-shape batched BAMG search engine (TPU-native, jit-compiled).

The host engine (`repro.core.engine.BAMGIndex`) walks the graph one query
at a time through Python, which is exact for I/O accounting but serializes
every per-query overhead.  This engine processes the *whole batch per
step* with only fixed-shape array ops, so one compilation serves the
lifetime of the server:

- **ADC tables** `(B, M, K)` are built for the whole batch at once, and
  entry selection scores them against the entry-candidate codes with the
  `repro.kernels.pq_adc` kernel (query-sensitive entries, DiskANN++-style:
  each query starts from its own best candidates, not a global medoid).
- **Candidate pool** is a pair of `(B, L)` id/dist arrays (plus a `(B, L)`
  expanded mask), kept sorted ascending by estimated distance.  Inserts are
  a vectorized insert-sort: concatenate `(B, L + R)`, stable-sort by id to
  drop duplicates (the incumbent pool entry wins, preserving its expanded
  flag), then stable-sort by distance and truncate to L.  No Python pool.
  The merge primitive lives in `repro.build.pool.pool_merge`; the
  batched construction frontier (`repro.build.frontier`) uses the same
  (B, L) pool shape with a leaner seen-mask-based merge.
- **Beam expansion** runs a fixed number of iterations (`max_hops`); each
  iteration pops the best unexpanded candidate of every row, gathers its
  padded adjacency row `(B, R)`, and ADC-scores the gathered neighbor codes
  `(B, R, M)` against the per-row tables.  Rows whose pool is exhausted
  no-op via masking (`-1` neighbors score `+inf` and never enter the pool).
  Under a `fused*` backend the whole loop instead runs as one VMEM-resident
  Pallas program (`repro.kernels.beam_fused`: frontier select, one-hot
  adjacency/code gathers, inlined rowwise ADC, and a sort-free ranked pool
  merge per hop) -- bit-identical pool ids by construction, no per-hop
  HBM round-trip.  `fused_stream*` keeps the corpus in HBM and DMAs only
  the rows each hop reads, so one engine serves shards larger than VMEM
  (bit-identical to the resident fused path); `backend="auto"`
  picks resident vs streaming on TPU via the `beam_fused.vmem_bytes`
  estimator.  The unfused path stays as the oracle, its per-stage
  kernels (`pq_adc`, `pq_adc_rowwise`) dispatched on the same backend knob.
- **Exact re-rank** gathers the raw vectors of each row's top `rerank` pool
  entries and merges through `repro.kernels.l2_topk.l2_topk_rowwise`.

Fixed-shape contract: one compilation per distinct `(B, D)` query shape and
`(k,)`; L, R, max_hops, rerank, and the entry-candidate count are baked at
engine construction.  Differences vs the host engine: no I/O simulation
(pure device compute), and beam expansion replaces the intra-block
alpha-BFS -- both explore the same monotonic graph, so results agree under
an exhaustive configuration (see tests/test_serve_engine.py).
"""
from __future__ import annotations

import copy
import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.build.pool import pool_merge as _pool_merge
from repro.core.pq import adc_tables as _adc_tables
from repro.kernels import beam_fused
from repro.kernels.beam_fused.ops import TILE_B, beam_hops
from repro.kernels.beam_fused.ops import resolve as resolve_hop_backend
from repro.kernels.l2_topk.ops import l2_topk_rowwise
from repro.kernels.pq_adc.ops import pq_adc, pq_adc_rowwise
from repro.utils.faults import InjectedFault

from . import telemetry

# backend -> the beam_hops backend the fused hop loop dispatches on
_FUSED_INNER = {"fused": "auto", "fused_pallas": "pallas",
                "fused_interpret": "interpret", "fused_ref": "ref",
                "fused_stream": "stream",
                "fused_stream_interpret": "stream_interpret"}
# the streaming modes only exist for the hop loop; per-stage kernels
# (pq_adc entry scoring) fall back to the matching resident backend
_STAGE_INNER = {"stream": "pallas", "stream_interpret": "interpret"}


def resolve_backend(backend: str, *, n: int, r: int, m: int, k: int = 256,
                    l: int = 64, max_hops: int = 32, tile_b: int = 8,
                    n_chunk: int = 2048, platform: Optional[str] = None,
                    budget: Optional[int] = None) -> str:
    """Resolve `EngineConfig.backend="auto"` to a concrete backend.

    On CPU/GPU: the unfused jnp path ("ref") -- zero behavior change for
    hosts without a TPU.  On TPU: the fused hop loop, VMEM-resident
    ("fused") when `beam_fused.vmem_bytes` fits the budget, HBM-streaming
    ("fused_stream") when the shard is too large to be VMEM-resident.
    Non-"auto" values pass through untouched.  Every value this returns
    is either "ref" or a `_FUSED_INNER` key, so auto can never fall
    through to an unresolvable backend (pinned by
    tests/test_serve_engine.py).
    """
    if backend != "auto":
        return backend
    if platform is None:
        platform = jax.default_backend()
    if platform != "tpu":
        return "ref"
    fits = beam_fused.fits_vmem(n, r, m=m, k=k, l=l, max_hops=max_hops,
                                tile_b=tile_b, n_chunk=n_chunk, budget=budget)
    return "fused" if fits else "fused_stream"


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    l: int = 64               # candidate pool capacity per query
    max_hops: int = 32        # fixed beam-expansion iterations
    n_entry: int = 4          # entry seeds per query
    rerank: Optional[int] = None   # pool prefix reranked exactly (None = l)
    n_entry_cands: int = 256  # entry candidate pool scored by pq_adc
    # kernel backend, reaching entry scoring AND the hop loop:
    #   "auto"             on TPU the fused kernel -- VMEM-resident when
    #                      `beam_fused.vmem_bytes` fits the budget,
    #                      HBM-streaming ("fused_stream") when the shard
    #                      is larger than VMEM (see `resolve_backend`);
    #                      unfused jnp ("ref") on CPU
    #   "pallas"/"interpret"/"ref"   unfused hop loop; per-stage kernels
    #                      (pq_adc entry scoring, pq_adc_rowwise neighbor
    #                      scoring) on the named pq_adc backend
    #   "fused"            one Pallas program for the whole hop loop
    #                      (repro.kernels.beam_fused; auto inner backend)
    #   "fused_pallas"/"fused_interpret"/"fused_ref"   fused loop pinned
    #                      to one beam_hops backend (parity/CI)
    #   "fused_stream"/"fused_stream_interpret"   the HBM-streaming fused
    #                      loop (row DMAs from the HBM corpus;
    #                      bit-identical pools to the resident fused path)
    backend: str = "auto"


@functools.partial(jax.jit, static_argnames=("k", "l", "max_hops", "n_entry",
                                             "rerank", "backend"))
def batched_search(x, adj, codes, codebooks, entry_cands, entry_codes,
                   queries, tomb, k: int, l: int, max_hops: int, n_entry: int,
                   rerank: int, backend: str, n_live=None):
    """One fixed-shape search step for a whole query batch.

    x (N, D) f32; adj (N, R) int32 VID neighbors, -1 pad; codes (N, M);
    codebooks (M, K, dsub); entry_cands (E,) int32 VIDs with their codes
    (E, M); queries (B, D); tomb (N,) bool tombstone mask (streaming
    freshness -- tombstoned VIDs stay navigable in the beam but are masked
    at the exact re-rank, so they can never reach the returned top-k; the
    mask is a traced argument, so flipping tombstones never recompiles).
    `n_live` (traced int32; None = all B) counts the real rows at the top
    of a padded batch: the fused ADC hop loop skips the tiles of rows
    >= n_live, whose answers are then empty.  Returns (ids (B, k) int32
    with -1 pad, dists (B, k) f32 ascending, hops_used (B,) int32).
    """
    b = queries.shape[0]
    queries = queries.astype(jnp.float32)
    backend = resolve_backend(backend, n=adj.shape[0], r=adj.shape[1],
                              m=codes.shape[1], k=codebooks.shape[1],
                              l=l, max_hops=max_hops)
    fused = backend in _FUSED_INNER
    inner = _FUSED_INNER.get(backend, backend)
    stage = _STAGE_INNER.get(inner, inner)

    # --- query-sensitive entry selection: pq_adc over the candidate pool
    with jax.named_scope("bamg.entry"):
        with jax.named_scope("bamg.adc_tables"):
            tables = _adc_tables(queries, codebooks)       # (B, M, K)
        ed = pq_adc(tables, entry_codes, backend=stage)    # (B, E)
        seed_neg, seed_idx = jax.lax.top_k(-ed, n_entry)
        seed_ids = entry_cands[seed_idx].astype(jnp.int32)  # (B, n_entry)

        pool_ids = jnp.full((b, l), -1, jnp.int32)
        pool_d = jnp.full((b, l), jnp.inf, jnp.float32)
        pool_exp = jnp.zeros((b, l), bool)
        pool_ids, pool_d, pool_exp = _pool_merge(
            pool_ids, pool_d, pool_exp, seed_ids, -seed_neg, l)

    rows = jnp.arange(b)
    codes_i = codes.astype(jnp.int32)

    with jax.named_scope("bamg.hop_loop"):
        if fused:
            # --- one VMEM-resident program for the whole hop loop
            pool_ids, pool_d, pool_exp, hops, *_ = beam_hops(
                adj, pool_ids, pool_d, pool_exp, max_hops,
                tables=tables, codes=codes_i, backend=inner, n_live=n_live)
        else:
            def step(state, _):
                pool_ids, pool_d, pool_exp, hops = state
                frontier_d = jnp.where(pool_exp | (pool_ids < 0), jnp.inf,
                                       pool_d)
                j = jnp.argmin(frontier_d, axis=1)         # (B,)
                has = jnp.isfinite(frontier_d[rows, j])
                v = jnp.where(has, pool_ids[rows, j], 0)
                pool_exp = pool_exp.at[rows, j].set(pool_exp[rows, j] | has)
                nbrs = jnp.where(has[:, None], adj[v], -1)  # (B, R)
                nd = pq_adc_rowwise(tables, codes_i[jnp.clip(nbrs, 0)],
                                    backend=inner)
                nd = jnp.where(nbrs >= 0, nd, jnp.inf)
                pool_ids, pool_d, pool_exp = _pool_merge(
                    pool_ids, pool_d, pool_exp, nbrs, nd, l)
                return (pool_ids, pool_d, pool_exp, hops + has), None

            (pool_ids, pool_d, pool_exp, hops), _ = jax.lax.scan(
                step, (pool_ids, pool_d, pool_exp, jnp.zeros(b, jnp.int32)),
                None, length=max_hops)

    # --- exact re-rank of each row's pool prefix (tombstones masked here:
    # the fused hop loop never sees the mask, so this covers every backend)
    with jax.named_scope("bamg.rerank"):
        cand = pool_ids[:, :rerank]                        # (B, C)
        vecs = x[jnp.clip(cand, 0)]                        # (B, C, D)
        valid = (cand >= 0) & ~tomb[jnp.clip(cand, 0)]
        dists, ridx = l2_topk_rowwise(queries, vecs, k, valid=valid)
        ids = jnp.take_along_axis(cand, ridx, axis=1)
        ids = jnp.where(jnp.isfinite(dists), ids, -1)
    return ids, dists, hops


class BatchedANNEngine:
    """Batched fixed-shape searcher over one BAMG sub-index.

    Construct via `from_index(BAMGIndex)` (uses `BAMGIndex.batch_arrays()`)
    or directly from the array dict.  `search_batch` accepts/returns numpy;
    the device round-trip and compilation cache are keyed on (B, D, k).
    """

    # arrays moved between mesh devices by place()/replicate()
    _ARRAY_ATTRS = ("x", "adj", "codes", "codebooks", "entry_cands",
                    "entry_codes", "tomb")

    def __init__(self, arrays: dict, config: Optional[EngineConfig] = None):
        self.config = config = config if config is not None else EngineConfig()
        self.n, self.d = arrays["x"].shape
        cands = np.asarray(arrays["entry_cands"], np.int64)
        self.x = jnp.asarray(arrays["x"], jnp.float32)
        self.adj = jnp.asarray(arrays["adj"], jnp.int32)
        self.codes = jnp.asarray(arrays["codes"])
        self.codebooks = jnp.asarray(arrays["codebooks"], jnp.float32)
        self.entry_cands = jnp.asarray(cands, jnp.int32)
        self.entry_codes = jnp.asarray(arrays["codes"][cands])
        self.tomb = jnp.zeros(self.n, bool)    # tombstone mask (freshness)
        l = min(config.l, self.n)
        self._l = l
        self._rerank = min(config.rerank if config.rerank is not None else l, l)
        self._n_entry = min(config.n_entry, len(cands))
        self._fault: Optional[Exception] = None
        # the last search_batch call's hops that expanded a node, (B,)
        # int32 on the host, and the hops its hop loop ran
        self.last_hops: Optional[np.ndarray] = None
        self.last_hops_run: Optional[int] = None
        # the hop-loop tiles that call ran, and its grid's tiles; None
        # where its hop loop skips no tile (`_hop_tiles`)
        self.last_tiles_run: Optional[int] = None
        self.last_tiles: Optional[int] = None

    @classmethod
    def from_index(cls, idx, config: Optional[EngineConfig] = None):
        config = config if config is not None else EngineConfig()
        return cls(idx.batch_arrays(n_entry_cands=config.n_entry_cands),
                   config)

    @property
    def rerank_capacity(self) -> int:
        """Largest k this engine can serve (pool prefix reranked exactly)."""
        return self._rerank

    def effective_rerank(self, l: Optional[int] = None) -> int:
        """Rerank capacity under an optional per-call pool override `l`."""
        if l is None:
            return self._rerank
        return min(self._rerank, max(1, min(int(l), self.n)))

    def place(self, device) -> "BatchedANNEngine":
        """device_put this engine's arrays onto `device`, in place.

        Identity is preserved so fault hooks (`inject_fault`) and the
        sharded front-end keep pointing at the served engine."""
        for a in self._ARRAY_ATTRS:
            setattr(self, a, jax.device_put(getattr(self, a), device))
        return self

    def replicate(self, device) -> "BatchedANNEngine":
        """A copy of this engine with its arrays device_put onto `device`.

        Used for the extra replicas of a shard's replica group; fault
        state is not shared with the original."""
        new = copy.copy(self)
        new._fault = None
        return new.place(device)

    @property
    def healthy(self) -> bool:
        return self._fault is None

    def inject_fault(self, exc: Optional[Exception] = None) -> None:
        """Fault hook: every subsequent `search_batch` raises `exc` (an
        `InjectedFault` by default: a dead shard) until `heal()` -- lets
        the sharded front-end's degraded-mode path be exercised without a
        real device failure."""
        self._fault = exc if exc is not None else InjectedFault(
            "injected engine fault")

    def heal(self) -> None:
        self._fault = None

    def set_tombstones(self, vids) -> None:
        """Replace the engine's tombstone mask (streaming freshness).

        `vids` is an iterable of VIDs to mask; out-of-range ids are
        ignored.  The mask is a traced jit argument, so this never
        triggers recompilation -- deletes take effect on the next call.
        """
        mask = np.zeros(self.n, bool)
        ids = np.asarray(list(vids), np.int64)
        if len(ids):
            ids = ids[(ids >= 0) & (ids < self.n)]
            mask[ids] = True
        self.tomb = jnp.asarray(mask)

    def _hop_tiles(self, l: int, max_hops: int, b: int, n_live: int):
        """(tiles run, tiles) of a call's hop loop: ceil(n_live / TILE_B)
        of ceil(b / TILE_B) where it runs a guarded Pallas kernel, else
        (None, None)."""
        dims = dict(n=self.n, r=self.adj.shape[1], m=self.codes.shape[1],
                    k=self.codebooks.shape[1], l=l, max_hops=max_hops)
        backend = resolve_backend(self.config.backend, **dims)
        if backend not in _FUSED_INNER:
            return None, None
        if resolve_hop_backend(_FUSED_INNER[backend], **dims) == "ref":
            return None, None
        return -(-n_live // TILE_B), -(-b // TILE_B)

    def search_batch(self, queries: np.ndarray, k: int, *,
                     l: Optional[int] = None, max_hops: Optional[int] = None,
                     exclude=None, rows: Optional[int] = None):
        """queries (B, D) -> (ids (B, k) int64 with -1 pad, dists (B, k)).

        `l` / `max_hops` optionally shrink the pool / hop budget for this
        call (adaptive beam width under a latency SLO -- see
        `repro.serve.runtime.scheduler`).  Both are static jit arguments,
        so each distinct override compiles once and is cached like any
        other shape; defaults reproduce the configured beam exactly.

        `exclude` masks additional VIDs for this call only (on top of any
        standing `set_tombstones` mask): excluded ids stay navigable but
        never appear in the returned top-k.  Accepts an iterable of VIDs
        or a (N,) bool mask.

        `rows` says that only the first `rows` queries are real and the
        rest pad the batch to its compiled shape (None = all): the fused
        hop loop skips the tiles of padding rows, whose answers are then
        empty.  It is a traced argument, so every fill runs one program.

        The call's hops per row are left in `last_hops`, the hops its hop
        loop ran in `last_hops_run`, and the tiles it ran and had in
        `last_tiles_run` and `last_tiles` (`_hop_tiles`).
        """
        if self._fault is not None:
            raise self._fault
        q = jnp.asarray(np.atleast_2d(queries), jnp.float32)
        if q.shape[1] != self.d:
            raise ValueError(f"query dim {q.shape[1]} != corpus dim {self.d}")
        l_eff = self._l if l is None else max(1, min(int(l), self.n))
        rerank = self.effective_rerank(l)
        hops = (self.config.max_hops if max_hops is None
                else max(1, int(max_hops)))
        if k > rerank:
            raise ValueError(
                f"k={k} exceeds the rerank capacity {rerank}; raise "
                f"EngineConfig.l/rerank (fixed at engine construction) or "
                f"the per-call l override")
        tomb = self.tomb
        if exclude is not None:
            if not isinstance(exclude, np.ndarray):
                exclude = sorted(exclude)       # sets/frozensets/iterables
            extra = np.asarray(exclude)
            if extra.dtype != bool:
                mask = np.zeros(self.n, bool)
                ids = extra.astype(np.int64).ravel()
                if len(ids):
                    ids = ids[(ids >= 0) & (ids < self.n)]
                    mask[ids] = True
                extra = mask
            tomb = tomb | jnp.asarray(extra)
        b = q.shape[0]
        n_live = b if rows is None else min(max(int(rows), 0), b)
        out = batched_search(
            self.x, self.adj, self.codes, self.codebooks, self.entry_cands,
            self.entry_codes, q, tomb, k=k, l=l_eff,
            max_hops=hops, n_entry=self._n_entry,
            rerank=rerank, backend=self.config.backend,
            n_live=np.int32(n_live))
        with telemetry.span(telemetry.DEVICE_WAIT):
            jax.block_until_ready(out)
        with telemetry.span(telemetry.FETCH):
            ids, dists, self.last_hops = jax.device_get(out)
        self.last_hops_run = hops
        self.last_tiles_run, self.last_tiles = self._hop_tiles(
            l_eff, hops, b, n_live)
        return ids.astype(np.int64), dists

    def memory_bytes(self) -> int:
        return sum(int(a.size) * a.dtype.itemsize
                   for a in (self.x, self.adj, self.codes, self.codebooks))
