"""Compile the serve path's Pallas kernels for a TPU v5e without a chip.

Interpret mode (the rest of the suite) cannot see what Mosaic, the TPU
kernel compiler, refuses: unaligned slices, lowerings it lacks, more VMEM
than a core has.  Here each `pallas_call` of the serve path, and the whole
`batched_search` step at N=1M and at the gist1m cell's widths (d=960, PQ
M=240), is lowered and compiled for a described `v5e:2x2` topology at
serving widths.  Nothing runs.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every pytest-xdist worker
imports every test file.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

# serving widths: query batch, pool, degree, PQ subspaces/centroids, hops
B, L, R, M, K, HOPS, D = 64, 64, 32, 16, 256, 32, 128
N_CHUNK = 2048
N_STREAM = 489 * N_CHUNK          # a 1M-row shard, padded to n_chunk
N_SEARCH = 1_000_000


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back without one:
    # keep the persistent cache out of it
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", prev)


@pytest.fixture(scope="module")
def spec(topo):
    from jax.sharding import SingleDeviceSharding
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    return make


def _largest_resident_n(**dims) -> int:
    """Largest n_chunk multiple the `auto` rule still serves resident."""
    from repro.kernels.beam_fused import fits_vmem
    n = N_CHUNK
    while fits_vmem(n + N_CHUNK, R, l=L, max_hops=HOPS, n_chunk=N_CHUNK,
                    **dims):
        n += N_CHUNK
    return n


def _lower(name, s):
    from repro.kernels.beam_fused import kernel as bk
    from repro.kernels.pq_adc import kernel as pk
    pool = (s((B, L)), s((B, L)), s((B, L)))
    n_adc = _largest_resident_n(m=M)
    n_l2 = _largest_resident_n(d=D)
    return {
        "pq_adc": lambda: jax.jit(pk.pq_adc_pallas).lower(
            s((B, M, K)), s((N_CHUNK, M), jnp.int32)),
        "pq_adc_rowwise": lambda: jax.jit(pk.pq_adc_rowwise_pallas).lower(
            s((B, M, K)), s((B, R, M), jnp.int32)),
        "beam_hops_adc_pallas": lambda: bk.beam_hops_adc_pallas.lower(
            s((n_adc, R)), s((n_adc, M)), s((B, M, K)), *pool, HOPS),
        "beam_hops_l2_pallas": lambda: bk.beam_hops_l2_pallas.lower(
            s((n_l2, R)), s((n_l2, D + 1)), s((B, D)), *pool, HOPS),
        "beam_hops_adc_stream": lambda: bk.beam_hops_adc_stream.lower(
            s((N_STREAM, R)), s((N_STREAM, M)), s((B, M, K)), *pool, HOPS),
        "beam_hops_l2_stream": lambda: bk.beam_hops_l2_stream.lower(
            s((N_STREAM, R)), s((N_STREAM, D + 1)), s((B, D)), *pool, HOPS),
    }[name]()


@pytest.mark.parametrize("name", [
    "pq_adc", "pq_adc_rowwise", "beam_hops_adc_pallas",
    "beam_hops_l2_pallas", "beam_hops_adc_stream", "beam_hops_l2_stream"])
def test_kernel_compiles_for_v5e(spec, name):
    """Each kernel compiles at serving widths; the resident beam programs
    at the largest corpus `fits_vmem` accepts, so the VMEM estimate the
    `auto` backend trusts is checked against the compiler."""
    compiled = _lower(name, spec).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m, l, hops", [
    (M, L, HOPS),        # EngineConfig defaults
    (64, 256, 256),      # chip_smoke.py's PQ M and beam
])
def test_batched_search_fused_stream_compiles_at_1m(spec, m, l, hops):
    """The whole serve step of one 1M-vector SIFT-shaped shard."""
    from repro.serve.ann_engine import batched_search
    s = spec
    step = functools.partial(batched_search.lower, k=10, l=l,
                             max_hops=hops, n_entry=4, rerank=l,
                             backend="fused_stream")
    compiled = step(s((N_SEARCH, D)), s((N_SEARCH, R), jnp.int32),
                    s((N_SEARCH, m), jnp.uint8), s((m, K, D // m)),
                    s((256,), jnp.int32), s((256, m), jnp.uint8),
                    s((B, D)), s((N_SEARCH,), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # x (512 MB) + adj + codes + tombstones are the arguments; the
    # lane-padded streaming copies dominate the temporaries
    assert mem.argument_size_in_bytes < 2 ** 30
    assert mem.temp_size_in_bytes < 4 * 2 ** 30


def _stream_scratch_avals(s, n, m=64):
    """The VMEM/SMEM scratch the streamed ADC kernel declares at the
    sift1m cell's widths (PQ M=64 unless given) over an n-row shard."""
    from repro.kernels.beam_fused import kernel as bk
    b, l = 64, 256
    jaxpr = jax.make_jaxpr(functools.partial(
        bk.beam_hops_adc_stream, max_hops=l))(
            s((n, R)), s((n, m)), s((b, m, K)), s((b, l)), s((b, l)),
            s((b, l)))
    (call,) = [e for e in _eqns(jaxpr.jaxpr) if e.primitive.name ==
               "pallas_call"]
    kernel = call.params["jaxpr"]
    n_scratch = call.params["grid_mapping"].num_scratch_operands
    return [str(v.aval) for v in kernel.invars[-n_scratch:]]


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jitted calls inside it."""
    for e in jaxpr.eqns:
        yield e
        sub = e.params.get("jaxpr")
        if e.primitive.name != "pallas_call" and sub is not None:
            yield from _eqns(getattr(sub, "jaxpr", sub))


def test_adc_stream_compiles_at_cell_widths_and_scratch_ignores_n(spec):
    """The streamed ADC hop loop compiles at the sift1m cell's widths
    (n=65,536, R=32, M=64, B=64, l = max_hops = 256), and its scratch is
    the same at 65,536 and 2^20 rows: the row gather does not grow with
    the shard."""
    from repro.kernels.beam_fused import kernel as bk
    s = spec
    n, b, l, m = 65_536, 64, 256, 64
    compiled = bk.beam_hops_adc_stream.lower(
        s((n, R)), s((n, m)), s((b, m, K)), s((b, l)), s((b, l)),
        s((b, l)), l).compile()
    assert "tpu_custom_call" in compiled.as_text()
    small = _stream_scratch_avals(s, n)
    # two row-gather sets of four, and the sub-space-major codes
    assert len(small) == 9
    assert small == _stream_scratch_avals(s, 2 ** 20)


# the gist1m cell's widths: n=65,536, B=64, l = max_hops = 256, R=32,
# K=256, 1,024 entry candidates, d=960
CELL_N, CELL_B, CELL_L, CELL_E, GIST_D = 65_536, 64, 256, 1_024, 960


@pytest.mark.parametrize("m", (64, 128, 240))
def test_adc_kernels_compile_at_pq_width(spec, m):
    """The streamed ADC hop loop and the entry-scoring kernel compile in
    the default scoped VMEM at PQ M=64 (sift1m), 128 and 240 (gist1m):
    their scoring holds one group of sub-spaces at a time, whatever M."""
    from repro.kernels.beam_fused import kernel as bk
    from repro.kernels.pq_adc import kernel as pk
    s = spec
    n, b, l = CELL_N, CELL_B, CELL_L
    hop = bk.beam_hops_adc_stream.lower(
        s((n, R)), s((n, m)), s((b, m, K)), s((b, l)), s((b, l)),
        s((b, l)), l).compile()
    assert "tpu_custom_call" in hop.as_text()
    entry = jax.jit(pk.pq_adc_pallas).lower(
        s((b, m, K)), s((CELL_E, m), jnp.int32)).compile()
    assert "tpu_custom_call" in entry.as_text()


def test_adc_stream_scratch_does_not_grow_with_m(spec):
    """The streamed ADC hop loop declares the same scratch at M=240 as at
    M=64: code rows arrive one 128-lane part at a time, and the
    sub-space-major codes scratch is 128 rows at any M."""
    assert (_stream_scratch_avals(spec, CELL_N, m=240)
            == _stream_scratch_avals(spec, CELL_N, m=64))


def test_batched_search_compiles_at_gist_widths(spec):
    """The whole serve step of the gist1m cell: D=960, PQ M=240 (4-dim
    sub-spaces), n=65,536, on the backend `auto` picks there."""
    from repro.serve.ann_engine import batched_search, resolve_backend
    s = spec
    n, m, l = CELL_N, 240, CELL_L
    backend = resolve_backend("auto", n=n, r=R, m=m, k=K, l=l, max_hops=l,
                              platform="tpu")
    assert backend == "fused_stream"
    compiled = batched_search.lower(
        s((n, GIST_D)), s((n, R), jnp.int32), s((n, m), jnp.uint8),
        s((m, K, GIST_D // m)), s((CELL_E,), jnp.int32),
        s((CELL_E, m), jnp.uint8), s((CELL_B, GIST_D)), s((n,), jnp.bool_),
        k=10, l=l, max_hops=l, n_entry=4, rerank=l,
        backend=backend).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("m, d", ((64, 128), (240, GIST_D)))
def test_adc_stream_with_live_rows_compiles(spec, m, d):
    """The streamed ADC hop loop with a traced live-row count (the
    scalar-prefetch operand, the guarded tile body and the input blocks
    clamped to the last live tile), alone and inside the whole serve
    step, at the sift1m (M=64) and gist1m (M=240, d=960) cells' widths."""
    from repro.kernels.beam_fused import kernel as bk
    from repro.serve.ann_engine import batched_search
    s = spec
    n, b, l = CELL_N, CELL_B, CELL_L
    n_live = s((), jnp.int32)
    hop = bk.beam_hops_adc_stream.lower(
        s((n, R)), s((n, m)), s((b, m, K)), s((b, l)), s((b, l)),
        s((b, l)), l, n_live=n_live).compile()
    assert "tpu_custom_call" in hop.as_text()
    step = batched_search.lower(
        s((n, d)), s((n, R), jnp.int32), s((n, m), jnp.uint8),
        s((m, K, d // m)), s((CELL_E,), jnp.int32), s((CELL_E, m), jnp.uint8),
        s((b, d)), s((n,), jnp.bool_), k=10, l=l, max_hops=l, n_entry=4,
        rerank=l, backend="fused_stream", n_live=n_live).compile()
    assert "tpu_custom_call" in step.as_text()
