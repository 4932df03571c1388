"""Distributed ring gather / scatter primitives vs dense oracles.

Subprocess-based (needs 8 fake devices before jax init), like
test_sharded.py.
"""
import os
import subprocess
import sys

FLAGS = "--xla_force_host_platform_device_count=8"


def _run(snippet: str, timeout=900):
    env = dict(os.environ, XLA_FLAGS=FLAGS, PYTHONPATH="src")
    r = subprocess.run([sys.executable, "-c", snippet], env=env,
                       capture_output=True, text=True, timeout=timeout,
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"


PRELUDE = """
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from jax.experimental.shard_map import shard_map
from repro.models.gnn.ring_gather import ring_gather, ring_scatter_add
from jax.sharding import AxisType
mesh = jax.make_mesh((2, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2)
rng = np.random.default_rng(0)
E, d, T = 64, 16, 200
table = jnp.asarray(rng.normal(size=(E, d)), jnp.float32)
idx = jnp.asarray(rng.integers(-1, E, (T,)), jnp.int32)
AX = ("data", "model")
"""


def test_ring_gather_fwd_and_vjp():
    _run(PRELUDE + """
def f(tab, ix):
    return shard_map(lambda t, i: ring_gather(t, i, AX), mesh=mesh,
                     in_specs=(P(AX, None), P(AX)), out_specs=P(AX, None),
                     check_rep=False)(tab, ix)
out = jax.jit(f)(table, idx)
ref = jnp.where(idx[:, None] >= 0, table[jnp.clip(idx, 0, E-1)], 0.0)
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-6)
g = jax.jit(jax.grad(lambda t: jnp.sum(f(t, idx) ** 2)))(table)
g_ref = jax.grad(lambda t: jnp.sum(jnp.where(
    idx[:, None] >= 0, t[jnp.clip(idx, 0, E-1)], 0.0) ** 2))(table)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5,
                           atol=1e-5)
print("ok")
""")


def test_ring_scatter_fwd_and_vjp():
    _run(PRELUDE + """
vals = jnp.asarray(rng.normal(size=(T, d)), jnp.float32)
def f(v, ix):
    return shard_map(lambda vv, i: ring_scatter_add(vv, i, AX, E // 8),
                     mesh=mesh, in_specs=(P(AX, None), P(AX)),
                     out_specs=P(AX, None), check_rep=False)(v, ix)
out = jax.jit(f)(vals, idx)
ref = jnp.zeros((E, d)).at[jnp.where(idx >= 0, idx, E)].add(
    jnp.where(idx[:, None] >= 0, vals, 0.0), mode="drop")
np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=1e-5,
                           atol=1e-5)
g = jax.jit(jax.grad(lambda v: jnp.sum(f(v, idx) ** 2)))(vals)
g_ref = jax.grad(lambda v: jnp.sum(jnp.zeros((E, d)).at[
    jnp.where(idx >= 0, idx, E)].add(
    jnp.where(idx[:, None] >= 0, v, 0.0), mode="drop") ** 2))(vals)
np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), rtol=1e-5,
                           atol=1e-5)
print("ok")
""")
