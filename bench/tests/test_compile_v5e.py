"""Each configuration's serve step, compiled for a described TPU v5e at its
real shapes (no chip needed): `batched_search` with the backend `auto`
takes on the chip, for the cell's batch of 64.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import functools
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import BENCH

B, K_CENT = 64, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


CONFIGS = json.loads((BENCH.parent / "BENCHMARK.json").read_text())["configs"]


@pytest.mark.parametrize("entry", CONFIGS, ids=[c["name"] for c in CONFIGS])
def test_serve_step_compiles_for_v5e(one_chip, entry):
    from repro.kernels.beam_fused import fits_vmem
    from repro.serve.ann_engine import batched_search

    c = json.loads((BENCH.parent / entry["file"]).read_text())
    n, d, r, m = c["n"], c["d"], c["build"]["r"], c["build"]["pq_m"]
    e = c["engine"]
    # `auto` on the chip: the shard is too large to be VMEM-resident
    assert not fits_vmem(n, r, m=m, k=K_CENT, l=e["l"], max_hops=e["max_hops"])

    def s(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    step = functools.partial(batched_search.lower, k=c["k"], l=e["l"],
                             max_hops=e["max_hops"], n_entry=4,
                             rerank=e["l"], backend="fused_stream")
    ne = e["n_entry_cands"]
    compiled = step(s((n, d)), s((n, r), jnp.int32), s((n, m), jnp.uint8),
                    s((m, K_CENT, d // m)), s((ne,), jnp.int32),
                    s((ne, m), jnp.uint8), s((B, d)),
                    s((n,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    print(c["name"], "argument bytes", mem.argument_size_in_bytes,
          "temp bytes", mem.temp_size_in_bytes)
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
