"""Quickstart: build a BAMG index, search it, inspect the I/O profile.

    PYTHONPATH=src python examples/quickstart.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np  # noqa: E402

from repro.core.engine import BAMGIndex, BAMGParams  # noqa: E402
from repro.data.synthetic import make_vector_dataset  # noqa: E402
from repro.utils.compile_cache import use_compile_cache  # noqa: E402


def main() -> None:
    use_compile_cache()
    # 1. a corpus with exact ground truth ------------------------------------
    ds = make_vector_dataset("quickstart", n=2000, d=64, nq=20, k_gt=10,
                             seed=0)

    # 2. build: NSG -> BNF block shuffling -> BAMG refinement (Alg. 2)
    #    -> multi-layer nav graph (Alg. 3) -> decoupled disk layout (Fig. 3)
    idx = BAMGIndex.build(ds.base, BAMGParams(alpha=3, beta=1.05))
    print(f"blocks: {idx.graph.members.shape[0]} x capacity "
          f"{idx.graph.capacity}, nav layers: {idx.nav.n_layers}")
    print(f"on-disk: graph {idx.store.graph_bytes/2**20:.1f} MiB + "
          f"vectors {idx.store.vector_bytes/2**20:.1f} MiB; "
          f"in-memory: {idx.memory_bytes()/2**20:.2f} MiB (PQ codes + nav)")

    # 3. search one query (Alg. 4: block-first, PQ-guided, exact re-rank)
    r = idx.search(ds.queries[0], k=10, l=40)
    print(f"query 0: {r.nio} block reads "
          f"({r.graph_reads} graph + {r.vector_reads} vector), "
          f"{r.hops} hops, ids={r.ids[:5].tolist()}...")

    # 4. batch evaluation against ground truth
    st = idx.search_batch(ds.queries, k=10, l=40, gt=ds.gt)
    print(f"recall@10={st.recall:.3f}  NIO={st.mean_nio:.1f}  "
          f"simulated QPS~{st.qps:.0f}")

    # 5a. pipelined I/O: batched submissions at queue depth 8.  NIO is
    #     identical by construction -- only the modeled service time drops.
    idx.configure_io(qd=8, batch_io=True)
    stp = idx.search_batch(ds.queries, k=10, l=40, gt=ds.gt)
    print(f"pipelined qd=8: NIO={stp.mean_nio:.1f} (unchanged)  "
          f"service={stp.mean_service_us:.0f}us vs "
          f"serial={stp.mean_serial_us:.0f}us  QPS~{stp.qps_pipelined:.0f}")
    assert stp.mean_nio == st.mean_nio

    # 5b. cache engineering: 2Q block cache + the hot navigation-entry
    #     graph blocks pinned in memory (Starling-style) -- this one *does*
    #     cut NIO, by turning the per-query entry reads into hits.
    idx.configure_io(cache_policy="2q", pin_nav_blocks=16)
    stq = idx.search_batch(ds.queries, k=10, l=40, gt=ds.gt)
    print(f"2q + pinned nav: NIO={stq.mean_nio:.1f}  "
          f"hit_rate={stq.cache_hit_rate:.2f}  QPS~{stq.qps_pipelined:.0f}")
    idx.configure_io(cache_policy="lru", qd=1, batch_io=False,
                     pin_nav_blocks=0)

    # 6. persistence
    idx.save("/tmp/bamg_quickstart.npz")
    idx2 = BAMGIndex.load("/tmp/bamg_quickstart.npz")
    r2 = idx2.search(ds.queries[0], k=10, l=40)
    assert np.array_equal(r.ids, r2.ids)
    print("save/load roundtrip OK")


if __name__ == "__main__":
    main()
