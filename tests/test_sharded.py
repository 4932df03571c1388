"""Distribution correctness: sharded paths vs single-device oracles.

These run in *subprocesses* so they can set
XLA_FLAGS=--xla_force_host_platform_device_count=8 before jax initializes
(the main test session keeps the real single-device view).
"""
import os
import subprocess
import sys

import pytest

FLAGS = "--xla_force_host_platform_device_count=8"


def _run(snippet: str, timeout=900):
    env = dict(os.environ, XLA_FLAGS=FLAGS, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from jax.sharding import AxisType
from repro.models.transformer import (LMConfig, ShardCtx, init_lm_params,
    lm_loss, serve_prefill, decode_step, init_cache, lm_param_specs,
    cache_specs)
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
ctx, ctx0 = ShardCtx(mesh=mesh), ShardCtx(mesh=None)
def put(tree, specs):
    return jax.tree.map(lambda x, s: jax.device_put(
        x, NamedSharding(mesh, s if s is not None else P())), tree, specs)
toks = jnp.asarray(np.random.default_rng(0).integers(0, 256, (4, 16)), jnp.int32)
labels = jnp.roll(toks, -1, axis=1)
td = jax.device_put(toks, NamedSharding(mesh, P("data", None)))
"""


def test_dense_tp_loss_matches_unsharded():
    _run(PRELUDE + """
cfg = LMConfig(name="tp", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
               d_head=16, d_ff=128, vocab=256, remat="none", loss_chunks=2,
               dtype="float32")
params = init_lm_params(cfg, jax.random.PRNGKey(0))
ps = put(params, lm_param_specs(cfg, ctx))
ls, _ = jax.jit(lambda p, t, l: lm_loss(p, cfg, t, l, ctx))(ps, td, labels)
lr, _ = jax.jit(lambda p, t, l: lm_loss(p, cfg, t, l, ctx0))(params, toks, labels)
np.testing.assert_allclose(float(ls), float(lr), rtol=2e-5)
print("dense TP ok")
""")


def test_fsdp_specs_loss_matches():
    _run(PRELUDE + """
cfg = LMConfig(name="f", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
               d_head=16, d_ff=128, vocab=256, remat="full", loss_chunks=2,
               dtype="float32")
params = init_lm_params(cfg, jax.random.PRNGKey(0))
ps = put(params, lm_param_specs(cfg, ctx, fsdp_axis="data"))
ls, _ = jax.jit(lambda p, t, l: lm_loss(p, cfg, t, l, ctx))(ps, td, labels)
lr, _ = jax.jit(lambda p, t, l: lm_loss(p, cfg, t, l, ctx0))(params, toks, labels)
np.testing.assert_allclose(float(ls), float(lr), rtol=2e-5)
print("fsdp ok")
""")


def test_moe_shard_map_matches_local_oracle():
    _run(PRELUDE + """
from repro.models.moe import MoEConfig
mcfg = LMConfig(name="m", n_layers=2, d_model=64, n_heads=8, n_kv_heads=4,
                d_head=16, d_ff=0, vocab=256, remat="none", loss_chunks=2,
                dtype="float32",
                moe=MoEConfig(n_experts=8, top_k=2, d_ff_expert=64,
                              n_shared=1, d_ff_shared=64, pad_multiple=4,
                              capacity_factor=8.0,
                              expert_capacity_factor=8.0, groups=2))
mp = init_lm_params(mcfg, jax.random.PRNGKey(1))
mps = put(mp, lm_param_specs(mcfg, ctx))
ls, _ = jax.jit(lambda p, t, l: lm_loss(p, mcfg, t, l, ctx))(mps, td, labels)
lr, _ = jax.jit(lambda p, t, l: lm_loss(p, mcfg, t, l, ctx0))(mp, toks, labels)
np.testing.assert_allclose(float(ls), float(lr), rtol=2e-5)
g = jax.jit(jax.grad(lambda p: lm_loss(p, mcfg, td, labels, ctx)[0]))(mps)
gn = sum(float(jnp.sum(jnp.abs(x))) for x in jax.tree.leaves(g))
assert np.isfinite(gn) and gn > 0
print("moe ok")
""")


def test_seq_sharded_decode_matches_local():
    _run(PRELUDE + """
dcfg = LMConfig(name="d", n_layers=2, d_model=64, n_heads=8, n_kv_heads=2,
                d_head=16, d_ff=128, vocab=256, remat="none", dtype="float32")
dp = init_lm_params(dcfg, jax.random.PRNGKey(2))
lg0, (ck, cv), lens = jax.jit(lambda p, t: serve_prefill(p, dcfg, t, ctx0))(dp, toks)
ck0, cv0, _ = init_cache(dcfg, 4, 32, dtype=jnp.float32)
ck0 = ck0.at[:, :, :16].set(ck); cv0 = cv0.at[:, :, :16].set(cv)
pos = jnp.asarray([16]*4, jnp.int32)
ref, _ = jax.jit(lambda p, t, q, c: decode_step(p, dcfg, t, q, c, ctx0, "local"))(
    dp, toks[:, :1], pos, (ck0, cv0, lens))
dps = put(dp, lm_param_specs(dcfg, ctx))
for mode in ("seq", "seq_all"):
    cs_k, cs_v, cs_l = cache_specs(dcfg, ctx, mode)
    cc = (jax.device_put(ck0, NamedSharding(mesh, cs_k)),
          jax.device_put(cv0, NamedSharding(mesh, cs_v)),
          jax.device_put(lens, NamedSharding(mesh, cs_l)))
    lg, nc = jax.jit(lambda p, t, q, c: decode_step(p, dcfg, t, q, c, ctx, mode))(
        dps, toks[:, :1], pos, cc)
    np.testing.assert_allclose(np.asarray(lg), np.asarray(ref),
                               rtol=3e-4, atol=3e-4)
    assert int(nc[2][0]) == 17
print("decode ok")
""")


def test_manual_dp_compressed_convergence():
    _run(PRELUDE + """
from repro.train.optimizer import AdamWConfig
from repro.train.trainer import make_manual_dp_step, make_train_step, init_train_state
from repro.data.synthetic import lm_batch
mesh1 = jax.make_mesh((8,), ("data",), axis_types=(AxisType.Auto,))
cfg = LMConfig(name="c", n_layers=2, d_model=32, n_heads=4, n_kv_heads=2,
               d_head=8, d_ff=64, vocab=64, remat="none", loss_chunks=2,
               dtype="float32")
ocfg = AdamWConfig(lr=1e-2, warmup_steps=5, total_steps=100)
ctx_n = ShardCtx(mesh=None)
def loss_fn(p, b):
    return lm_loss(p, cfg, b["tokens"], b["labels"], ctx_n)
def bf(s):
    t, l = lm_batch(s, 16, 8, cfg.vocab, seed=0)
    return {"tokens": jnp.asarray(t), "labels": jnp.asarray(l)}
params = init_lm_params(cfg, jax.random.PRNGKey(0))
ref_step = make_train_step(loss_fn, ocfg, donate=False)
st = init_train_state(params, ocfg)
for i in range(10):
    st, m_ref = ref_step(st, bf(i))
st8 = init_train_state(params, ocfg, ef=True)
dp_step = make_manual_dp_step(loss_fn, ocfg, mesh1, compression="int8_ef")
for i in range(10):
    st8, m_c = dp_step(st8, bf(i))
assert abs(float(m_ref["loss"]) - float(m_c["loss"])) < 0.05
print("manual dp ok")
""")


def test_sharded_embedding_lookup_matches():
    _run(PRELUDE + """
from repro.models.recsys.embedding import sharded_lookup
table = jnp.asarray(np.random.default_rng(3).normal(size=(64, 6)), jnp.float32)
ids = jnp.asarray(np.random.default_rng(4).integers(0, 64, (4, 5)), jnp.int32)
tput = jax.device_put(table, NamedSharding(mesh, P("model", None)))
out = jax.jit(lambda t, i: sharded_lookup(t, i, mesh, "model", ("data",)))(
    tput, jax.device_put(ids, NamedSharding(mesh, P("data", None))))
np.testing.assert_allclose(np.asarray(out), np.asarray(table)[np.asarray(ids)],
                           rtol=1e-6)
print("embedding ok")
""")
