"""Public index API: build / save / load / search for the three compared
systems (DiskANN, Starling-style, BAMG), all on the same I/O simulator.

    idx = BAMGIndex.build(x, BAMGParams(alpha=3, beta=1.05))
    res = idx.search(q, k=10, l=64)          # one query
    out = idx.search_batch(queries, k=10, l=64)  # stats aggregated

This is the host (exact-semantics) engine: one Python query at a time, every
block fetch routed through the I/O simulator so NIO/recall match the paper's
accounting.

I/O knobs (all three systems; see `repro.core.io_sim` for the two metric
domains):

* ``cache_policy`` ('lru' | 'fifo' | 'clock' | '2q') and ``cache_blocks``
  select the block-cache replacement policy and capacity; BAMG additionally
  has ``vec_cache_blocks`` for the decoupled vector region and
  ``pin_nav_blocks`` -- a budget of hot navigation-entry graph blocks pinned
  in memory forever (Starling-style; pins count against ``cache_blocks``).
* ``qd`` is the io_uring-style queue depth of the pipelined `IOScheduler`;
  ``batch_io=True`` makes search issue batched submissions (per-hop frontier
  prefetch + one-shot re-rank reads).  Accounting (NIO, recall, cache hits)
  is bit-identical to the serial path -- only `BatchStats.mean_service_us`
  (pipelined) vs `mean_serial_us` (sequential) and the derived
  `qps_pipelined` change.
* ``search_batch(..., warm_cache=True)`` keeps the block cache warm across
  the queries of a batch (cross-query serving mode); the default cold cache
  per query matches the paper's NIO accounting.

The TPU-native batched engine lives in
`repro.serve.ann_engine.BatchedANNEngine` -- it consumes the fixed-shape
arrays exported by `BAMGIndex.batch_arrays()` and processes a whole query
batch per jitted step (no I/O simulation; pure device compute).  The
scatter-gather front-end over sharded sub-indexes is
`repro.serve.frontend.ShardedFrontend`.  Search-path knobs (`l`, `max_hops`)
mean the same thing in both engines.
"""
from __future__ import annotations

import dataclasses
import io
import time
from typing import Optional, Sequence

import numpy as np

from repro.build import BuildConfig, GraphBuilder
from repro.utils.faults import FaultPlan, FaultSpec, RetryPolicy

from .bamg import BAMGGraph
from .block_assign import bnf_blocks, block_members
from .distances import recall_at_k
from .graph_build import build_vamana, degree_stats
from .io_sim import BLOCK_SIZE, CostModel
from .navgraph import (NavGraph, build_navgraph, nav_pin_gblocks, search_nav)
from .pq import PQCodec, train_pq
from .search import SearchResult, search_bamg, search_coupled
from .storage import (CoupledStorage, DecoupledStorage, coupled_nodes_per_block,
                      max_capacity_for)


def _batch(search_one, queries, gt, k: int, cost: CostModel,
           warm_cache: bool) -> BatchStats:
    """Shared batch loop: `search_one(i, q, drop_cache)` per query; a warm
    cache drops only before the first query (cross-query serving mode)."""
    res = [search_one(i, q, (not warm_cache) or i == 0)
           for i, q in enumerate(queries)]
    return _aggregate(res, gt, k, cost)


# configure_io sentinel: None is a meaningful value for the fault/deadline
# knobs (it *disables* them), so "leave unchanged" needs its own marker
_KEEP = object()


def _update_io_params(p, updates: dict, keep_updates: dict | None = None) -> None:
    """None-means-unchanged in-place update of an index's params; entries in
    `keep_updates` use the _KEEP sentinel instead (None is meaningful)."""
    for name, val in updates.items():
        if val is not None:
            setattr(p, name, val)
    for name, val in (keep_updates or {}).items():
        if val is not _KEEP:
            setattr(p, name, val)


def _fault_plan(p) -> Optional[FaultPlan]:
    """The index's seeded fault plan (None when fault injection is off)."""
    return FaultPlan(p.faults, seed=p.fault_seed) if p.faults is not None else None


def _cost_for(p) -> CostModel:
    return CostModel(qd=p.qd, timeout_us=p.timeout_us, hedge_us=p.hedge_us)


def _configure_coupled_io(idx, cache_policy, cache_blocks, qd, batch_io,
                          faults=_KEEP, fault_seed=None, retry=_KEEP,
                          timeout_us=_KEEP, hedge_us=_KEEP):
    """Rebuild only the coupled storage/scheduler with new I/O knobs (the
    graph, PQ codes, and layout are untouched) -- cheap sweeps."""
    _update_io_params(idx.params, dict(
        cache_policy=cache_policy, cache_blocks=cache_blocks, qd=qd,
        batch_io=batch_io, fault_seed=fault_seed),
        dict(faults=faults, retry=retry, timeout_us=timeout_us,
             hedge_us=hedge_us))
    p = idx.params
    idx.store = CoupledStorage(idx.x, idx.adj, order=idx.store.layout,
                               policy=p.cache_policy,
                               cache_blocks=p.cache_blocks,
                               cost=_cost_for(p), faults=_fault_plan(p),
                               retry=p.retry)
    idx.cost = idx.store.scheduler.cost
    return idx


def _builder_for(params) -> GraphBuilder:
    """GraphBuilder from an index params dataclass (`build_backend`:
    "host" keeps the numpy reference pipeline, "batched" routes the
    expensive stages through `repro.build`'s jit'd fixed-shape programs)."""
    knn = getattr(params, "build_knn", "clustered")  # BAMG-only knob:
    # Vamana (DiskANN/Starling) has no kNN stage, so only BAMGParams
    # carries it
    return GraphBuilder(BuildConfig(backend=params.build_backend,
                                    batch_size=params.build_batch,
                                    knn_mode=knn))


def _pick_pq_m(d: int, target: int | None = None) -> int:
    """Largest M <= target dividing d (PQ subspace count).

    Default target scales with dimension (~d/16, clamped to [16, 64]) --
    high-d corpora need more subspaces or ADC noise swamps the distance
    ordering (faiss uses the same ballpark)."""
    if target is None:
        target = min(64, max(16, d // 16))
    for m in range(min(target, d), 0, -1):
        if d % m == 0:
            return m
    return 1


@dataclasses.dataclass
class BatchStats:
    recall: float
    mean_nio: float
    mean_graph_reads: float
    mean_vector_reads: float
    mean_hops: float
    mean_n_dist: float
    mean_n_pq: float
    qps: float
    mean_service_us: float = 0.0   # pipelined I/O wall-clock (qd-overlapped)
    mean_serial_us: float = 0.0    # same demand misses, strictly serial
    cache_hit_rate: float = 0.0    # hits / (hits + NIO) over the batch
    qps_pipelined: float = 0.0     # QPS with the pipelined service time
    # resilience (fault injection; all zero on a clean run)
    degraded_fraction: float = 0.0  # queries that lost >=1 block to faults
    mean_failed_reads: float = 0.0  # undeliverable blocks skipped per query
    mean_retries: float = 0.0       # extra read attempts per query
    mean_hedges: float = 0.0        # hedged duplicate reads per query
    p99_service_us: float = 0.0     # tail of the pipelined service time


def _aggregate(results: list[SearchResult], gt: Optional[np.ndarray], k: int,
               cost: CostModel) -> BatchStats:
    nio = float(np.mean([r.nio for r in results]))
    nd = float(np.mean([r.n_dist for r in results]))
    npq = float(np.mean([r.n_pq for r in results]))
    rec = -1.0
    if gt is not None:
        idm = np.full((len(results), k), -1, np.int64)   # short results pad
        for i, r in enumerate(results):
            m = min(k, len(r.ids))
            idm[i, :m] = r.ids[:m]
        rec = recall_at_k(idm, gt, k)
    service_all = np.asarray([r.service_us for r in results], np.float64)
    service = float(service_all.mean())
    hits = float(np.sum([r.cache_hits for r in results]))
    total_nio = float(np.sum([r.nio for r in results]))
    return BatchStats(
        recall=rec, mean_nio=nio,
        mean_graph_reads=float(np.mean([r.graph_reads for r in results])),
        mean_vector_reads=float(np.mean([r.vector_reads for r in results])),
        mean_hops=float(np.mean([r.hops for r in results])),
        mean_n_dist=nd, mean_n_pq=npq, qps=cost.qps(nio, nd, npq),
        mean_service_us=service,
        mean_serial_us=float(np.mean([r.serial_us for r in results])),
        cache_hit_rate=hits / (hits + total_nio) if hits + total_nio else 0.0,
        qps_pipelined=cost.qps_from_io_us(service, nd, npq),
        degraded_fraction=float(np.mean([r.degraded for r in results])),
        mean_failed_reads=float(np.mean([r.failed_reads for r in results])),
        mean_retries=float(np.mean([r.retries for r in results])),
        mean_hedges=float(np.mean([r.hedges for r in results])),
        p99_service_us=float(np.percentile(service_all, 99)))


# ---------------------------------------------------------------------------
# Baselines
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class DiskANNParams:
    r: int = 32
    l_build: int = 64
    alpha: float = 1.2
    pq_m: Optional[int] = None
    cache_policy: str = "lru"        # block-cache replacement policy
    cache_blocks: int = 256          # block-cache capacity
    qd: int = 1                      # I/O queue depth (pipelined scheduler)
    batch_io: bool = False           # batched submissions + prefetch
    build_backend: str = "host"      # graph construction: "host" | "batched"
    build_batch: int = 256           # nodes per batched-build step
    faults: Optional[FaultSpec] = None   # fault injection (None = clean disk)
    fault_seed: int = 0              # seed of the deterministic fault plan
    retry: Optional[RetryPolicy] = None  # bounded-retry policy (None = default)
    timeout_us: Optional[float] = None   # abandon an attempt past this
    hedge_us: Optional[float] = None     # duplicate-read hedge age
    seed: int = 0


class DiskANNIndex:
    """Vamana graph + coupled layout in graph order + Alg. 1 search."""

    kind = "diskann"

    def __init__(self, x, adj, entry, codec, codes, store, params=None):
        self.x, self.adj, self.entry = x, adj, entry
        self.codec, self.codes, self.store = codec, codes, store
        self.params = params if params is not None else DiskANNParams()
        self.cost = store.scheduler.cost

    @classmethod
    def build(cls, x: np.ndarray, params: DiskANNParams = DiskANNParams()):
        params = dataclasses.replace(params)   # configure_io mutates in place
        adj, entry = _builder_for(params).build_vamana(
            x, r=params.r, l_build=params.l_build, alpha=params.alpha,
            seed=params.seed)
        m = params.pq_m or _pick_pq_m(x.shape[1])
        codec = train_pq(x, m=m, seed=params.seed)
        codes = codec.encode(x)
        store = CoupledStorage(x, adj, policy=params.cache_policy,
                               cache_blocks=params.cache_blocks,
                               cost=_cost_for(params),
                               faults=_fault_plan(params), retry=params.retry)
        return cls(x, adj, entry, codec, codes, store, params)

    def configure_io(self, cache_policy: Optional[str] = None,
                     cache_blocks: Optional[int] = None,
                     qd: Optional[int] = None,
                     batch_io: Optional[bool] = None,
                     faults=_KEEP, fault_seed: Optional[int] = None,
                     retry=_KEEP, timeout_us=_KEEP,
                     hedge_us=_KEEP) -> "DiskANNIndex":
        """Rebuild only the storage/scheduler with new I/O knobs."""
        return _configure_coupled_io(self, cache_policy, cache_blocks, qd,
                                     batch_io, faults=faults,
                                     fault_seed=fault_seed, retry=retry,
                                     timeout_us=timeout_us, hedge_us=hedge_us)

    def search(self, q: np.ndarray, k: int, l: int,
               drop_cache: bool = True,
               exclude: Optional[set] = None) -> SearchResult:
        table = self.codec.adc_table(q)
        bs = max(2, self.params.qd) if self.params.batch_io else None
        return search_coupled(self.store, self.codes, table, q, self.entry,
                              k, l, block_level=False, batch_submit=bs,
                              drop_cache=drop_cache, exclude=exclude)

    def search_batch(self, queries: np.ndarray, k: int, l: int,
                     gt: Optional[np.ndarray] = None,
                     warm_cache: bool = False,
                     exclude: Optional[set] = None) -> BatchStats:
        return _batch(lambda i, q, dc: self.search(q, k, l, drop_cache=dc,
                                                   exclude=exclude),
                      queries, gt, k, self.cost, warm_cache)

    def degree_stats(self):
        blocks = (self.store.pos // self.store.npb).astype(np.int64)
        return degree_stats(self.adj, blocks)

    def index_bytes(self) -> int:
        return self.store.device.total_bytes

    def memory_bytes(self) -> int:
        return self.codes.nbytes + self.codec.codebooks.nbytes


@dataclasses.dataclass
class StarlingParams:
    r: int = 32
    l_build: int = 64
    alpha: float = 1.2
    pq_m: Optional[int] = None
    nav_sample: float = 0.05     # random in-memory nav sample fraction
    cache_policy: str = "lru"
    cache_blocks: int = 256
    qd: int = 1
    batch_io: bool = False
    build_backend: str = "host"  # graph construction: "host" | "batched"
    build_batch: int = 256       # nodes per batched-build step
    faults: Optional[FaultSpec] = None   # fault injection (None = clean disk)
    fault_seed: int = 0              # seed of the deterministic fault plan
    retry: Optional[RetryPolicy] = None  # bounded-retry policy (None = default)
    timeout_us: Optional[float] = None   # abandon an attempt past this
    hedge_us: Optional[float] = None     # duplicate-read hedge age
    seed: int = 0


class StarlingIndex:
    """Vamana graph + BNF block-shuffled coupled layout + block-level search
    + random-sample in-memory navigation graph (Starling [38])."""

    kind = "starling"

    def __init__(self, x, adj, entry, codec, codes, store, nav_vids, nav_adj,
                 params=None):
        self.x, self.adj, self.entry = x, adj, entry
        self.codec, self.codes, self.store = codec, codes, store
        self.nav_vids, self.nav_adj = nav_vids, nav_adj
        self.params = params if params is not None else StarlingParams()
        self.cost = store.scheduler.cost

    @classmethod
    def build(cls, x: np.ndarray, params: StarlingParams = StarlingParams()):
        params = dataclasses.replace(params)   # configure_io mutates in place
        adj, entry = _builder_for(params).build_vamana(
            x, r=params.r, l_build=params.l_build, alpha=params.alpha,
            seed=params.seed)
        npb = coupled_nodes_per_block(x.shape[1], params.r)
        blocks = bnf_blocks(adj, npb, seed=params.seed)
        order = np.argsort(blocks, kind="stable").astype(np.int64)
        m = params.pq_m or _pick_pq_m(x.shape[1])
        codec = train_pq(x, m=m, seed=params.seed)
        codes = codec.encode(x)
        store = CoupledStorage(x, adj, order=order,
                               policy=params.cache_policy,
                               cache_blocks=params.cache_blocks,
                               cost=_cost_for(params),
                               faults=_fault_plan(params), retry=params.retry)
        # Starling nav graph: random sample + Vamana over the sample
        rng = np.random.default_rng(params.seed)
        ns = max(16, int(len(x) * params.nav_sample))
        nav_vids = np.sort(rng.choice(len(x), size=min(ns, len(x)), replace=False))
        if len(nav_vids) > 8:
            nav_adj, _ = build_vamana(x[nav_vids], r=min(16, len(nav_vids) - 1),
                                      l_build=32, alpha=1.2, seed=params.seed)
        else:
            nav_adj = -np.ones((len(nav_vids), 1), np.int32)
        return cls(x, adj, entry, codec, codes, store, nav_vids, nav_adj,
                   params)

    def configure_io(self, cache_policy: Optional[str] = None,
                     cache_blocks: Optional[int] = None,
                     qd: Optional[int] = None,
                     batch_io: Optional[bool] = None,
                     faults=_KEEP, fault_seed: Optional[int] = None,
                     retry=_KEEP, timeout_us=_KEEP,
                     hedge_us=_KEEP) -> "StarlingIndex":
        """Rebuild only the storage/scheduler with new I/O knobs."""
        return _configure_coupled_io(self, cache_policy, cache_blocks, qd,
                                     batch_io, faults=faults,
                                     fault_seed=fault_seed, retry=retry,
                                     timeout_us=timeout_us, hedge_us=hedge_us)

    def _nav_entries(self, table: np.ndarray, n_entry: int = 4) -> list[int]:
        # greedy over the sampled nav graph using PQ distances
        from .navgraph import NavLayer, _greedy_layer
        layer = NavLayer(vids=self.nav_vids.astype(np.int64), adj=self.nav_adj, entry=0)

        def pq_dist(vids):
            c = self.codes[vids].astype(np.int64)
            return table[np.arange(table.shape[0])[None, :], c].sum(1)

        ids, _ = _greedy_layer(layer, [0], pq_dist, ef=16)
        return [int(self.nav_vids[i]) for i in ids[:n_entry]] or [self.entry]

    def search(self, q: np.ndarray, k: int, l: int,
               drop_cache: bool = True,
               exclude: Optional[set] = None) -> SearchResult:
        table = self.codec.adc_table(q)
        entries = self._nav_entries(table)
        bs = max(2, self.params.qd) if self.params.batch_io else None
        return search_coupled(self.store, self.codes, table, q, entries,
                              k, l, block_level=True, batch_submit=bs,
                              drop_cache=drop_cache, exclude=exclude)

    def search_batch(self, queries: np.ndarray, k: int, l: int,
                     gt: Optional[np.ndarray] = None,
                     warm_cache: bool = False,
                     exclude: Optional[set] = None) -> BatchStats:
        return _batch(lambda i, q, dc: self.search(q, k, l, drop_cache=dc,
                                                   exclude=exclude),
                      queries, gt, k, self.cost, warm_cache)

    def degree_stats(self):
        blocks = (self.store.pos // self.store.npb).astype(np.int64)
        return degree_stats(self.adj, blocks)

    def index_bytes(self) -> int:
        return self.store.device.total_bytes

    def memory_bytes(self) -> int:
        # Starling keeps an id<->block map in memory (paper §5.2.5)
        return (self.codes.nbytes + self.codec.codebooks.nbytes
                + self.nav_adj.nbytes + self.nav_vids.nbytes
                + self.store.pos.nbytes + self.store.layout.nbytes
                + self.x.shape[1] * 4 * len(self.nav_vids))  # nav raw vectors


# ---------------------------------------------------------------------------
# BAMG
# ---------------------------------------------------------------------------
def _make_decoupled_store(x, graph, nav, p) -> DecoupledStorage:
    """Decoupled storage from a built graph + the I/O knobs in params."""
    pins = ()
    if p.pin_nav_blocks > 0:
        budget = min(p.pin_nav_blocks, max(0, p.cache_blocks))
        pins = nav_pin_gblocks(nav, graph.blocks, budget, entry=graph.entry)
    return DecoupledStorage(
        x, graph.adj, graph.blocks, graph.members,
        cache_blocks=p.cache_blocks, vec_cache_blocks=p.vec_cache_blocks,
        policy=p.cache_policy,
        vec_policy=p.vec_cache_policy, pinned_gblocks=pins,
        cost=_cost_for(p), faults=_fault_plan(p), retry=p.retry)


@dataclasses.dataclass
class BAMGParams:
    alpha: int = 3
    beta: float = 1.05
    r: int = 32
    l_build: int = 64
    knn_k: int = 32
    gamma: int = 256
    capacity: Optional[int] = None   # default: max for 4 KB graph block
    pq_m: Optional[int] = None
    use_nav: bool = True
    use_bmrng_prune: bool = True     # ablation: BAMG w/o BMRNG rule
    sibling_edges: bool = True
    cache_policy: str = "lru"        # graph block cache policy
    vec_cache_policy: Optional[str] = None   # default: same as cache_policy
    cache_blocks: int = 256          # graph block cache capacity
    vec_cache_blocks: int = 256      # vector block cache capacity
    qd: int = 1                      # I/O queue depth (pipelined scheduler)
    batch_io: bool = False           # batched submissions (top-alpha + rerank)
    pin_nav_blocks: int = 0          # nav-entry graph blocks pinned in memory
    build_backend: str = "host"      # graph construction: "host" | "batched"
    build_batch: int = 256           # nodes per batched-build step
    build_knn: str = "clustered"     # batched kNN stage: "clustered"|"exact"
    faults: Optional[FaultSpec] = None   # fault injection (None = clean disk)
    fault_seed: int = 0              # seed of the deterministic fault plan
    retry: Optional[RetryPolicy] = None  # bounded-retry policy (None = default)
    timeout_us: Optional[float] = None   # abandon an attempt past this
    hedge_us: Optional[float] = None     # duplicate-read hedge age
    seed: int = 0


class BAMGIndex:
    """The paper's system: BAMG graph + decoupled layout + nav graph +
    block-first search (Alg. 2/3/4)."""

    kind = "bamg"

    def __init__(self, x, graph: BAMGGraph, codec, codes, store, nav, params):
        self.x, self.graph = x, graph
        self.codec, self.codes, self.store = codec, codes, store
        self.nav = nav
        self.params = params
        self.cost = store.scheduler.cost
        # seconds per construction stage, in build order (set by `build`)
        self.build_seconds: dict[str, float] = {}

    @classmethod
    def build(cls, x: np.ndarray, params: BAMGParams = BAMGParams()):
        p = dataclasses.replace(params)        # configure_io mutates in place
        builder = _builder_for(p)
        seconds, t0 = {}, time.perf_counter()

        def lap(stage: str) -> None:
            nonlocal t0
            t1 = time.perf_counter()
            seconds[stage], t0 = t1 - t0, t1

        nsg_adj, entry = builder.build_nsg(x, r=p.r, l_build=p.l_build,
                                           knn_k=p.knn_k, seed=p.seed)
        lap("nsg")
        capacity = p.capacity or max_capacity_for(p.r)
        blocks = bnf_blocks(nsg_adj, capacity, seed=p.seed)
        lap("bnf_blocks")
        if p.use_bmrng_prune:
            graph = builder.refine_bamg(x, nsg_adj, entry, blocks, capacity,
                                        alpha=p.alpha, beta=p.beta,
                                        sibling_edges=p.sibling_edges,
                                        max_degree=p.r)
        else:  # ablation: same layout, no block-aware pruning
            graph = BAMGGraph(adj=nsg_adj, blocks=np.asarray(blocks, np.int32),
                              members=block_members(blocks, capacity),
                              entry=entry, capacity=capacity,
                              alpha=p.alpha, beta=p.beta)
        lap("refine_bamg")
        m = p.pq_m or _pick_pq_m(x.shape[1])
        codec = train_pq(x, m=m, seed=p.seed)
        codes = codec.encode(x)
        lap("pq")
        nav = None
        if p.use_nav:
            nav = build_navgraph(x, graph, alpha=p.alpha, beta=p.beta,
                                 gamma=p.gamma, capacity=capacity, seed=p.seed,
                                 build=builder.build_bamg)
        lap("navgraph")
        store = _make_decoupled_store(x, graph, nav, p)
        lap("store")
        idx = cls(x, graph, codec, codes, store, nav, p)
        idx.build_seconds = seconds
        return idx

    @classmethod
    def from_graph(cls, x: np.ndarray, graph: BAMGGraph,
                   params: BAMGParams = BAMGParams()) -> "BAMGIndex":
        """Index from an already-built BAMG graph (streaming consolidation:
        the graph comes out of delta-fold + Alg-2 refine, not a fresh
        `build`).  Trains PQ, builds the nav graph, and lays out storage
        exactly as `build` would."""
        p = dataclasses.replace(params)        # configure_io mutates in place
        m = p.pq_m or _pick_pq_m(x.shape[1])
        codec = train_pq(x, m=m, seed=p.seed)
        codes = codec.encode(x)
        nav = None
        if p.use_nav:
            nav = build_navgraph(x, graph, alpha=p.alpha, beta=p.beta,
                                 gamma=p.gamma, capacity=graph.capacity,
                                 seed=p.seed,
                                 build=_builder_for(p).build_bamg)
        store = _make_decoupled_store(x, graph, nav, p)
        return cls(x, graph, codec, codes, store, nav, p)

    def configure_io(self, cache_policy: Optional[str] = None,
                     vec_cache_policy: Optional[str] = None,
                     cache_blocks: Optional[int] = None,
                     vec_cache_blocks: Optional[int] = None,
                     qd: Optional[int] = None,
                     batch_io: Optional[bool] = None,
                     pin_nav_blocks: Optional[int] = None,
                     faults=_KEEP, fault_seed: Optional[int] = None,
                     retry=_KEEP, timeout_us=_KEEP,
                     hedge_us=_KEEP) -> "BAMGIndex":
        """Rebuild only the storage/scheduler with new I/O knobs (graph, PQ
        codes, and nav graph untouched) -- cheap policy/QD/pinning sweeps."""
        _update_io_params(self.params, dict(
            cache_policy=cache_policy, vec_cache_policy=vec_cache_policy,
            cache_blocks=cache_blocks, vec_cache_blocks=vec_cache_blocks,
            qd=qd, batch_io=batch_io, pin_nav_blocks=pin_nav_blocks,
            fault_seed=fault_seed),
            dict(faults=faults, retry=retry, timeout_us=timeout_us,
                 hedge_us=hedge_us))
        self.store = _make_decoupled_store(self.x, self.graph, self.nav,
                                           self.params)
        self.cost = self.store.scheduler.cost
        return self

    def _pq_dist_fn(self, table: np.ndarray):
        m_sub = table.shape[0]

        def fn(vids: np.ndarray) -> np.ndarray:
            c = self.codes[np.asarray(vids, np.int64)].astype(np.int64)
            return table[np.arange(m_sub)[None, :], c].sum(1)
        return fn

    def entries_for(self, table: np.ndarray, n_entry: int = 4) -> list[int]:
        if self.nav is not None and self.nav.layers:
            seeds, _ = search_nav(self.nav, self._pq_dist_fn(table), n_entry)
            if seeds:
                return seeds
        return [self.graph.entry]

    def search(self, q: np.ndarray, k: int, l: int,
               alpha: Optional[int] = None,
               rerank_margin: Optional[float] = None,
               random_entry_seed: Optional[int] = None,
               max_hops: Optional[int] = None,
               batch_io: Optional[bool] = None,
               drop_cache: bool = True,
               exclude: Optional[set] = None) -> SearchResult:
        table = self.codec.adc_table(q)
        if random_entry_seed is not None:  # ablation "BAMG w/o NG"
            rng = np.random.default_rng(random_entry_seed)
            entries = rng.choice(len(self.x), size=4, replace=False).tolist()
        else:
            entries = self.entries_for(table)
        a = alpha if alpha is not None else self.params.alpha
        batched = self.params.batch_io if batch_io is None else batch_io
        # batched mode: each pop submits the top-alpha unchecked candidates'
        # graph blocks together (demand + speculative prefetch)
        bs = max(2, a) if batched else None
        return search_bamg(self.store, self.codes, table, q, entries, k, l,
                           alpha=a, rerank_margin=rerank_margin,
                           max_hops=max_hops, batch_submit=bs,
                           drop_cache=drop_cache, exclude=exclude)

    def search_batch(self, queries: np.ndarray, k: int, l: int,
                     gt: Optional[np.ndarray] = None,
                     alpha: Optional[int] = None,
                     rerank_margin: Optional[float] = None,
                     random_entry: bool = False,
                     max_hops: Optional[int] = None,
                     batch_io: Optional[bool] = None,
                     warm_cache: bool = False,
                     exclude: Optional[set] = None) -> BatchStats:
        return _batch(
            lambda i, q, dc: self.search(
                q, k, l, alpha=alpha, rerank_margin=rerank_margin,
                random_entry_seed=(i if random_entry else None),
                max_hops=max_hops, batch_io=batch_io, drop_cache=dc,
                exclude=exclude),
            queries, gt, k, self.cost, warm_cache)

    def batch_arrays(self, n_entry_cands: int = 256) -> dict:
        """Fixed-shape numpy views for the batched TPU engine.

        Returns adjacency as padded `(N, R)` neighbor VIDs (-1 pad), the PQ
        codes/codebooks, the raw vectors, and `entry_cands`: a pool of entry
        candidate VIDs for query-sensitive entry selection (the finest nav
        layer when a navigation graph was built, else an evenly strided
        sample), capped at `n_entry_cands` by even striding so candidates
        stay spread across the corpus.
        """
        if self.nav is not None and self.nav.layers:
            cands = np.asarray(self.nav.layers[-1].vids, np.int64)
        else:
            cands = np.arange(len(self.x), dtype=np.int64)
        if len(cands) > n_entry_cands:
            cands = cands[np.linspace(0, len(cands) - 1, n_entry_cands,
                                      dtype=np.int64)]
        return {
            "x": np.asarray(self.x, np.float32),
            "adj": np.asarray(self.graph.adj, np.int32),
            "codes": np.asarray(self.codes, np.uint8),
            "codebooks": np.asarray(self.codec.codebooks, np.float32),
            "entry_cands": cands,
        }

    def degree_stats(self):
        return degree_stats(self.graph.adj, self.graph.blocks)

    def index_bytes(self) -> int:
        return self.store.graph_bytes + self.store.vector_bytes

    def memory_bytes(self) -> int:
        nav = self.nav.memory_bytes() if self.nav else 0
        return self.codes.nbytes + self.codec.codebooks.nbytes + nav

    # --- persistence --------------------------------------------------------
    def save(self, path: str) -> None:
        nav_layers = self.nav.layers if self.nav else []
        blobs = {
            "x": self.x, "adj": self.graph.adj, "blocks": self.graph.blocks,
            "members": self.graph.members,
            "entry": np.asarray(self.graph.entry),
            "capacity": np.asarray(self.graph.capacity),
            "alpha": np.asarray(self.params.alpha),
            "beta": np.asarray(self.params.beta),
            "codebooks": self.codec.codebooks, "codes": self.codes,
            "n_nav": np.asarray(len(nav_layers)),
        }
        for i, layer in enumerate(nav_layers):
            blobs[f"nav{i}_vids"] = layer.vids
            blobs[f"nav{i}_adj"] = layer.adj
            blobs[f"nav{i}_entry"] = np.asarray(layer.entry)
        np.savez_compressed(path, **blobs)

    @classmethod
    def load(cls, path: str) -> "BAMGIndex":
        from .navgraph import NavLayer
        with np.load(path) as z:
            x = z["x"]
            graph = BAMGGraph(adj=z["adj"], blocks=z["blocks"],
                              members=z["members"], entry=int(z["entry"]),
                              capacity=int(z["capacity"]),
                              alpha=int(z["alpha"]), beta=float(z["beta"]))
            codec = PQCodec(codebooks=z["codebooks"])
            codes = z["codes"]
            layers = [NavLayer(vids=z[f"nav{i}_vids"], adj=z[f"nav{i}_adj"],
                               entry=int(z[f"nav{i}_entry"]))
                      for i in range(int(z["n_nav"]))]
        params = BAMGParams(alpha=graph.alpha, beta=graph.beta,
                            capacity=graph.capacity)
        nav = NavGraph(layers=layers) if layers else None
        store = _make_decoupled_store(x, graph, nav, params)
        return cls(x, graph, codec, codes, store, nav, params)
