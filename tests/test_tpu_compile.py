"""Compile the serve kernels for a described TPU v5e at smollm-360m widths.

Nothing runs here. Each test lowers a kernel wrapper with ``interpret=False``
and compiles it with the TPU compiler for a chip described by
``jax.experimental.topologies``, which refuses what Mosaic cannot lower: a
dynamic slice of a value, an unaligned dynamic offset, a block whose last two
dims break the (8, 128) tiling, a shape cast it cannot lay out, or more VMEM
than a kernel may use. The interpret-mode parity tests cannot see any of
these. Shapes are the smollm-360m serve shapes (``configs/smollm_360m.py``):
decode M = 4 slots, prefill M = a 32-token chunk, the projections' (K, N),
and paged attention over a 512-token context in 16-token blocks.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests; the persistent compilation cache is off
around these compiles (an entry compiled for a described chip cannot be read
back without one).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops

DECODE_M, PREFILL_M = 4, 32
# q/o, k/v, gate/up and down projections of smollm-360m
PROJ_KN = ((960, 960), (960, 320), (960, 2560), (2560, 960))
HQ, HKV, HD = 15, 5, 64
SLOTS, MAX_LEN, BLOCK, CHUNK = 4, 512, 16, 32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        was = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", was)
            compilation_cache.reset_cache()


def _compile(fn, *shapes):
    """Compile ``fn`` for the described chip; assert a Mosaic kernel is in
    the program (not an XLA fallback)."""
    compiled = jax.jit(fn).lower(*shapes).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis() is not None
    return compiled


def _spec(dev, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=dev)


@pytest.mark.parametrize("m", [DECODE_M, PREFILL_M], ids=["decode", "prefill"])
def test_cac_hw_compiles_at_360m_widths(one_chip, m):
    for k, n in PROJ_KN:
        f32 = lambda shape: _spec(one_chip, shape, jnp.float32)
        _compile(lambda x, t, s: ops.cac_matmul(x, t, s, interpret=False),
                 f32((m, k)), f32((k, n)), f32((k, n)))


def test_bnn_packed_compiles_at_360m_widths(one_chip):
    for m in (DECODE_M, PREFILL_M):
        for k, n in PROJ_KN:
            _compile(lambda x, wp: ops.bnn_matmul_packed(x, wp, interpret=False),
                     _spec(one_chip, (m, k), jnp.float32),
                     _spec(one_chip, (k // 8, n), jnp.uint8))


def test_qnn8_compiles_at_360m_widths(one_chip):
    for m in (DECODE_M, PREFILL_M):
        for k, n in PROJ_KN:
            _compile(lambda x, w, s: ops.qnn_matmul(x, w, s, 0.05, interpret=False),
                     _spec(one_chip, (m, k), jnp.int8),
                     _spec(one_chip, (k, n), jnp.int8),
                     _spec(one_chip, (1, n), jnp.float32))


def _paged(dev, b, c, quantized):
    t = MAX_LEN // BLOCK
    n_phys = SLOTS * t + 1
    kv_dtype = jnp.int8 if quantized else jnp.float32
    shapes = [_spec(dev, (b, c, HQ, HD), jnp.float32),
              _spec(dev, (n_phys, BLOCK, HKV, HD), kv_dtype),
              _spec(dev, (n_phys, BLOCK, HKV, HD), kv_dtype),
              _spec(dev, (b, t), jnp.int32),
              _spec(dev, (b, c), jnp.int32)]
    if quantized:
        shapes += [_spec(dev, (n_phys, BLOCK, HKV, 1), jnp.float32)] * 2
        fn = lambda q, k, v, tb, p, ks, vs: ops.paged_attention(
            q, k, v, tb, p, k_scale=ks, v_scale=vs, interpret=False)
    else:
        fn = lambda q, k, v, tb, p: ops.paged_attention(q, k, v, tb, p,
                                                        interpret=False)
    _compile(fn, *shapes)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp_kv", "int8_kv"])
def test_paged_attn_decode_compiles_at_360m_widths(one_chip, quantized):
    _paged(one_chip, SLOTS, 1, quantized)


def test_paged_attn_prefill_chunk_compiles_at_360m_widths(one_chip):
    for quantized in (False, True):
        _paged(one_chip, 1, CHUNK, quantized)
