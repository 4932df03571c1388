"""Streaming freshness: inserts/deletes over a live BAMG index.

    PYTHONPATH=src python examples/fresh_serving.py

The FreshDiskANN pattern over BAMG (`repro.index.delta`): the disk
index stays frozen; writes land in an in-memory overlay -- inserts are
wired by incremental RobustPrune into copy-on-write adjacency rows,
deletes become tombstones that stay navigable but can never surface.
Every query is served *unified* (frozen base + overlay, one exact
top-k), so a write is visible on the very next read.  A background
`consolidate()` folds the overlay into a fresh build -- edge repair
around deleted nodes, then BNF block re-assignment + block-aware
refinement -- and publishes it through the blue/green deployment
lifecycle: reads never pause, and the swap is atomic.
"""
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core.distances import exact_knn  # noqa: E402
from repro.core.engine import BAMGParams  # noqa: E402
from repro.data.synthetic import make_vector_dataset  # noqa: E402
from repro.index.delta import DeltaParams, FreshService  # noqa: E402
from repro.serve import EngineConfig  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402

K, L = 10, 48


def recall(svc, queries, k=K):
    live_x, live_ext = svc.live_corpus()
    _, rows = exact_knn(live_x, queries, k)
    gt = live_ext[rows]
    ids, _ = svc.search_batch(queries, k, l=L)
    hits = sum(len(set(r.tolist()) & set(g.tolist()))
               for r, g in zip(ids, gt))
    return hits / (len(gt) * k)


def main() -> None:
    use_compile_cache()
    ds = make_vector_dataset("fresh", n=2000, d=32, nq=16, k_gt=K,
                             n_clusters=16, seed=0)
    svc = FreshService(tempfile.mkdtemp(prefix="fresh-"),
                       params=BAMGParams(r=16, l_build=32, seed=0),
                       config=EngineConfig(l=L, max_hops=24),
                       delta_params=DeltaParams(r=16, ef=48))

    t0 = time.time()
    svc.bootstrap(ds.base, "gen-0")
    print(f"gen-0: built+published+promoted {len(ds.base)} vectors "
          f"in {time.time()-t0:.0f}s (ACTIVE={svc.manager.active()})")

    # --- writes are visible on the next read --------------------------------
    rng = np.random.default_rng(1)
    new = (ds.base[rng.integers(0, len(ds.base), 100)]
           + 0.02 * rng.standard_normal((100, 32)).astype(np.float32))
    t0 = time.time()
    ext = svc.insert_batch(new)
    print(f"inserted 100 vectors in {time.time()-t0:.2f}s "
          f"(overlay={svc.delta.memory_bytes()/2**10:.0f} KiB)")
    ids, d = svc.search_batch(new[0][None, :], K)
    assert ids[0, 0] == ext[0]
    print(f"new vector findable immediately: id={ids[0, 0]} d={d[0, 0]:.4f}")

    victim = int(ds.gt[0, 0])              # the top-1 of query 0
    svc.delete(victim)
    svc.delete(int(ext[1]))                # deleting fresh writes works too
    ids, _ = svc.search_batch(ds.queries, K)
    assert victim not in set(ids.ravel().tolist())
    print(f"deleted id {victim} gone from results on the next read; "
          f"unified recall@{K}={recall(svc, ds.queries):.3f}")

    # --- consolidation: fold the overlay, swap blue/green -------------------
    t0 = time.time()
    svc.consolidate("gen-1", queries=ds.queries, k=K, min_recall=0.5,
                    keep_builds=2)
    print(f"gen-1: consolidated {svc.n_live} live vectors in "
          f"{time.time()-t0:.0f}s -- published, validated "
          f"(recall={svc.last_validation_recall:.3f}), promoted, hot-swapped")
    print(f"post-swap recall@{K}={recall(svc, ds.queries):.3f}; "
          f"builds kept: {svc.manager.builds()} "
          f"(rollback target {svc.manager.rollback_target()})")

    ids, _ = svc.search_batch(new[0][None, :], K)
    assert ids[0, 0] == ext[0], "external ids are stable across the swap"
    print("external ids stable across id-space compaction -- done")


if __name__ == "__main__":
    main()
