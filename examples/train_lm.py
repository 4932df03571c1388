"""End-to-end driver: train a ~100M-parameter LM for a few hundred steps
with checkpoint/restart (deliverable b).

    PYTHONPATH=src python examples/train_lm.py --steps 300

~100M params: 8 layers, d_model=512, 8 heads, d_ff=2048, vocab=32000.
On the CPU container this runs a reduced step count by default; pass
--steps 300 for the full demo.  Restart safety: kill it mid-run and rerun
-- it resumes from the latest checkpoint and the loss curve continues
exactly (stateless step-indexed data, train/ft.py).
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from repro.utils.compile_cache import use_compile_cache  # noqa: E402


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_lm100m")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.data.synthetic import lm_batch
    from repro.models.transformer import (LMConfig, ShardCtx, init_lm_params,
                                          lm_loss)
    from repro.train import checkpoint as ckpt
    from repro.train.ft import FTConfig, run_loop, resume_or_init
    from repro.train.optimizer import AdamWConfig
    from repro.train.trainer import init_train_state, make_train_step

    cfg = LMConfig(name="lm100m", n_layers=8, d_model=512, n_heads=8,
                   n_kv_heads=8, d_head=64, d_ff=2048, vocab=32000,
                   remat="none", loss_chunks=8, dtype="float32")
    ctx = ShardCtx(mesh=None)
    opt = AdamWConfig(lr=6e-4, warmup_steps=30, total_steps=args.steps)

    def loss_fn(params, batch):
        return lm_loss(params, cfg, batch["tokens"], batch["labels"], ctx)

    def batch_fn(step):
        t, l = lm_batch(step, args.batch, args.seq, cfg.vocab, seed=0)
        return {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}

    def init_fn():
        params = init_lm_params(cfg, jax.random.PRNGKey(0))
        n = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
        print(f"params: {n/1e6:.1f}M")
        return init_train_state(params, opt)

    ft = FTConfig(ckpt_dir=args.ckpt_dir, ckpt_every=25)
    state = resume_or_init(init_fn, ft)
    start = int(state["step"])
    if start:
        print(f"resumed from step {start}")
    step_fn = make_train_step(loss_fn, opt, donate=False)
    t0 = time.time()
    state, logs = run_loop(state, step_fn, batch_fn, args.steps, ft,
                           log_every=10)
    for s, m in logs:
        print(f"step {s:4d}  loss {m['loss']:.4f}  gnorm {m['grad_norm']:.2f}")
    dt = (time.time() - t0) / max(args.steps - start, 1)
    print(f"done ({dt*1e3:.0f} ms/step); checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
