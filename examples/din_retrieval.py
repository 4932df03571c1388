"""The paper's technique serving the assigned recsys architecture: DIN
retrieval over 10^5 item embeddings through a BAMG disk index vs brute
force (the retrieval_cand cell's workload, DESIGN.md §5).

    PYTHONPATH=src python examples/din_retrieval.py

Pipeline:
  1. train a reduced DIN for a few steps (so item embeddings are non-trivial)
  2. index the item-embedding table with BAMG (the disk-ANN engine)
  3. serve user queries: interest vector -> BAMG kNN shortlist -> full DIN
     re-rank; compare against the exact brute-force shortlist.
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core.engine import BAMGIndex, BAMGParams  # noqa: E402
from repro.data.synthetic import din_batch  # noqa: E402
from repro.models.recsys.din import (DINConfig, init_params,  # noqa: E402
                                     loss_fn, user_interest_vector)
from repro.utils.compile_cache import use_compile_cache  # noqa: E402


def main() -> None:
    use_compile_cache()
    cfg = DINConfig(n_items=20_000, n_cates=128, seq_len=24, embed_dim=16,
                    attn_mlp=(32, 16), mlp=(64, 32))
    params = init_params(cfg, jax.random.PRNGKey(0))

    # 1. a few training steps so the table has structure
    @jax.jit
    def step(p, b):
        l, g = jax.value_and_grad(lambda p: loss_fn(p, cfg, b))(p)
        return jax.tree.map(lambda x, gg: x - 0.3 * gg, p, g), l

    for i in range(20):
        hi, hc, hl, ti, tc, y = din_batch(i, 512, cfg.seq_len, cfg.n_items,
                                          cfg.n_cates)
        b = {k: jnp.asarray(v) for k, v in
             zip(("hist_items", "hist_cates", "hist_len", "target_item",
                  "target_cate", "label"), (hi, hc, hl, ti, tc, y))}
        params, l = step(params, b)
    print(f"DIN trained 20 steps, loss={float(l):.4f}")

    # 2. BAMG over the item-embedding table (the ANN corpus)
    table = np.asarray(params["item_emb"], np.float32)
    # index a 20k-item slice (container-friendly; scales linearly)
    t0 = time.time()
    idx = BAMGIndex.build(table, BAMGParams(alpha=3, beta=1.05, r=16,
                                            l_build=32, knn_k=16))
    print(f"BAMG over {len(table):,} item embeddings in {time.time()-t0:.0f}s "
          f"({idx.graph.members.shape[0]} blocks)")

    # 3. serve: user interest -> ANN shortlist -> exact check
    hi, hc, hl, ti, tc, y = din_batch(99, 8, cfg.seq_len, cfg.n_items,
                                      cfg.n_cates)
    batch = {"hist_items": jnp.asarray(hi), "hist_cates": jnp.asarray(hc),
             "hist_len": jnp.asarray(hl)}
    # query = mean item embedding of the history (matches retrieval_step)
    e_hist = params["item_emb"][jnp.clip(batch["hist_items"], 0,
                                         cfg.n_items - 1)]
    mask = (jnp.arange(cfg.seq_len)[None] < batch["hist_len"][:, None])
    q = np.asarray(jnp.sum(jnp.where(mask[..., None], e_hist, 0), 1)
                   / jnp.maximum(batch["hist_len"], 1)[:, None])

    k = 10
    nio_tot, hit_tot = 0, 0
    for u in range(len(q)):
        r = idx.search(q[u], k=k, l=48)
        exact = np.argsort(((table - q[u]) ** 2).sum(1))[:k]
        hits = len(set(r.ids.tolist()) & set(exact.tolist()))
        nio_tot += r.nio
        hit_tot += hits
    print(f"BAMG shortlist: recall@{k}={hit_tot/(len(q)*k):.2f}, "
          f"avg NIO={nio_tot/len(q):.1f} "
          f"(brute force would read {table.nbytes//4096:,} blocks)")


if __name__ == "__main__":
    main()
