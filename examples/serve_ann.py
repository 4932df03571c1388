"""Batched ANN serving: scatter-gather over sharded BAMG sub-indexes.

    PYTHONPATH=src python examples/serve_ann.py

The distributed serving pattern of DESIGN.md §4: the corpus is partitioned
into S sub-corpora (one per model-parallel shard at scale); each shard
builds its own BAMG sub-index independently (elastic: add/remove shards =
rebuild only the moved partitions); a query batch fans out as ONE batched
`repro.serve.ann_engine` call per shard and the per-shard top-k merge to a
global top-k in a single pass -- the TPU analogue of the paper's "every
I/O pays for itself", with per-query Python overhead amortized over the
whole batch.  The old per-query host loop is kept as the baseline.

Since the runtime refactor the fan-out is a *compiled instruction stream*
(SCATTER / RUN / GATHER / MERGE) interpreted over a placed shard fleet;
the tail of this demo prints the program and drives the continuous-
batching scheduler over an open-loop arrival timeline (p50/p99 vs SLO).
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core.distances import recall_at_k  # noqa: E402
from repro.core.engine import BAMGParams  # noqa: E402
from repro.data.synthetic import make_vector_dataset  # noqa: E402
from repro.serve import (EngineConfig, Scheduler,  # noqa: E402
                         SchedulerConfig, ShardedFrontend, make_requests,
                         summarize)
from repro.utils.compile_cache import use_compile_cache  # noqa: E402


def main() -> None:
    use_compile_cache()
    n_shards = 4
    k = 10
    ds = make_vector_dataset("serve", n=4000, d=64, nq=32, k_gt=10, seed=0)
    params = BAMGParams(alpha=3, beta=1.05, r=16, l_build=32, knn_k=16)

    t0 = time.time()
    frontend = ShardedFrontend.build(ds.base, n_shards, params=params,
                                     config=EngineConfig(l=24, max_hops=24))
    print(f"{n_shards} BAMG sub-indexes built in {time.time()-t0:.0f}s "
          f"(independent -> elastic scale-out)")

    # --- batched path: one engine call per shard, one global merge ---------
    frontend.search_batch(ds.queries, k=k)        # compile + warm
    t0 = time.time()
    ids, _ = frontend.search_batch(ds.queries, k=k)
    batched_s = time.time() - t0
    n_q = len(ds.queries)
    print(f"batched: recall@{k}={recall_at_k(ids, ds.gt, k):.3f}, "
          f"{batched_s/n_q*1e3:.2f} ms/query "
          f"({n_q/batched_s:.0f} qps, one call per shard per batch)")

    # --- host baseline: per-query per-shard Python loop ---------------------
    tops = []
    nio = 0
    t0 = time.time()
    for q in ds.queries:
        cand_ids, cand_d = [], []
        for vids, idx in zip(frontend.shard_vids, frontend.host_indexes):
            r = idx.search(q, k=k, l=24)
            cand_ids.append(vids[r.ids])
            cand_d.append(r.dists)
            nio += r.nio
        all_ids = np.concatenate(cand_ids)
        all_d = np.concatenate(cand_d)
        tops.append(all_ids[np.argsort(all_d)[:k]])
    host_s = time.time() - t0
    print(f"host loop: recall@{k}={recall_at_k(np.stack(tops), ds.gt, k):.3f}, "
          f"NIO/query (summed over shards)={nio/n_q:.1f}, "
          f"{host_s/n_q*1e3:.1f} ms/query -> batched speedup "
          f"{host_s/batched_s:.1f}x")

    # --- the runtime underneath: compiled program + request scheduler ------
    rt = frontend.runtime
    prog = " ".join(f"{ins.op.name}({ins.shard})" if ins.shard >= 0
                    else ins.op.name for ins in rt.program)
    print(f"\ncompiled serving program ({rt.n_shards} shards, "
          f"{rt.health()['n_workers']} worker(s)): {prog}")

    slo = 0.5
    sched = Scheduler(rt, SchedulerConfig(k=k, max_batch=16, slo=slo))
    reqs = make_requests(ds.queries, qps=100.0, slo=slo, n=96, seed=0)
    s = summarize(sched.run(reqs))
    print(f"scheduler @100 qps offered, SLO={slo*1e3:.0f}ms: "
          f"p50={s['p50_ms']:.1f}ms p99={s['p99_ms']:.1f}ms "
          f"deadline_hit={s['deadline_hit']:.2f} "
          f"shrunk_frac={s['shrunk_frac']:.2f} "
          f"({s['achieved_qps']:.0f} qps achieved)")


if __name__ == "__main__":
    main()
