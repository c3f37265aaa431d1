"""Compile the serve kernels for a described TPU v5e at smollm-360m widths.

Apart from one chip-only test, nothing runs here. Each test lowers a
kernel wrapper with ``interpret=False`` and compiles it with the TPU
compiler for a chip described by
``jax.experimental.topologies``, which refuses what Mosaic cannot lower: a
dynamic slice of a value, an unaligned dynamic offset, a block whose last two
dims break the (8, 128) tiling, a shape cast it cannot lay out, or more VMEM
than a kernel may use. The interpret-mode parity tests cannot see any of
these. Shapes are the smollm-360m serve shapes (``configs/smollm_360m.py``):
decode M = 4 slots, prefill M = a 32-token chunk, the projections' (K, N),
and paged attention over a 512-token context in 16-token blocks.

The last section compiles the paged scheduler's decode tick and prefill
chunk at the benchmark cells' shapes and checks that neither copies the KV
pool; its chip-only test also runs them on a placed pool.

The topology is described inside a module fixture, never at import, so every
test worker collects the same tests; the persistent compilation cache is off
around these compiles (an entry compiled for a described chip cannot be read
back without one).
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import ops
from repro.models.base import PagedKVLayout

DECODE_M, PREFILL_M = 4, 32
# q/o, k/v, gate/up and down projections of smollm-360m
PROJ_KN = ((960, 960), (960, 320), (960, 2560), (2560, 960))
HQ, HKV, HD = 15, 5, 64
SLOTS, MAX_LEN, BLOCK, CHUNK = 4, 512, 16, 32
ROW_LANES = PagedKVLayout(1, SLOTS * MAX_LEN // BLOCK + 1, BLOCK, HKV, HD).row_lanes


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
              _spec(dev, (n_phys, BLOCK, ROW_LANES), kv_dtype),
              _spec(dev, (n_phys, BLOCK, ROW_LANES), kv_dtype),
              _spec(dev, (b, t), jnp.int32),
              _spec(dev, (b, c), jnp.int32)]
    if quantized:
        shapes += [_spec(dev, (n_phys, BLOCK, HKV), jnp.float32)] * 2
        fn = lambda q, k, v, tb, p, ks, vs: ops.paged_attention(
            q, k, v, tb, p, n_kv_heads=HKV, k_scale=ks, v_scale=vs, interpret=False)
    else:
        fn = lambda q, k, v, tb, p: ops.paged_attention(q, k, v, tb, p, n_kv_heads=HKV,
                                                        interpret=False)
    _compile(fn, *shapes)


@pytest.mark.parametrize("quantized", [False, True], ids=["fp_kv", "int8_kv"])
def test_paged_attn_decode_compiles_at_360m_widths(one_chip, quantized):
    _paged(one_chip, SLOTS, 1, quantized)


def test_paged_attn_prefill_chunk_compiles_at_360m_widths(one_chip):
    for quantized in (False, True):
        _paged(one_chip, 1, CHUNK, quantized)


# ---------------------------------------------------------------------------
# The paged serve programs read the KV pool in place
# ---------------------------------------------------------------------------

# The benchmark cells' serve shapes: (config, slots, positions per slot)
SERVE_CELLS = {"smollm-360m": (64, 2048), "phi3-mini-3.8b": (8, 1024)}


def _pool_copies(hlo: str, pool) -> list:
    """Lines of the optimized HLO that copy a k/v pool leaf or one layer of
    it (into another layout, or its stacked ys)."""
    shapes = set()
    for name in ("k", "v"):
        shape = tuple(pool[name].shape)
        shapes |= {",".join(map(str, shape)), ",".join(map(str, shape[1:]))}
    pat = re.compile(r"= \w+\[([\d,]+)\]\{[^}]*\} copy(?:-start)?\(")
    return [line.strip()[:160] for line in hlo.splitlines()
            if (m := pat.search(line)) and m.group(1) in shapes]


def _peak_line(label: str, compiled) -> str:
    ma = compiled.memory_analysis()
    gb = lambda n: f"{n / 1e9:.3f}"
    peak = (ma.argument_size_in_bytes + ma.output_size_in_bytes - ma.alias_size_in_bytes
            + ma.temp_size_in_bytes)
    return (f"{label}: peak {gb(peak)} GB = arguments {gb(ma.argument_size_in_bytes)} + "
            f"outputs {gb(ma.output_size_in_bytes)} - aliased {gb(ma.alias_size_in_bytes)} + "
            f"temporaries {gb(ma.temp_size_in_bytes)}")


def _serve_model(cfg_name: str, n_layers: int):
    """The model as the benchmark serves it: bika, no remat."""
    from repro.configs import get_config
    from repro.models import build_model

    arch = get_config(cfg_name, n_layers=n_layers, compute_mode="bika", remat=False)
    return arch, build_model(arch, phase="serve")


def _compile_programs(sched, params, pool, n_slots: int, max_len: int, dev=None):
    """The scheduler's decode tick and prefill chunk, compiled for operands
    of the given serve shapes (blocks of 16, chunks of 32, matmuls at
    "highest" as the benchmark serves them); the index operands are
    abstract, placed on ``dev`` when given."""
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=dev)
    t = max_len // 16
    with jax.default_matmul_precision("highest"):
        tick = sched._tick_fn.lower(params, pool, i32(n_slots), i32(n_slots),
                                    i32(n_slots, t)).compile()
        chunk = sched.prefill.fn().lower(params, pool, i32(1, 32), i32(1, t), i32(1),
                                         i32(1)).compile()
    return {"decode tick": tick, "prefill chunk": chunk}


def _abstract_programs(cfg_name: str, n_layers: int, n_slots: int, max_len: int, dev=None):
    """The programs at the given serve shapes with every operand abstract
    (placed on ``dev`` when given): nothing is allocated at those shapes,
    the programs come from a scheduler over a two-slot pool."""
    from repro.nn.module import unbox
    from repro.serve.scheduler import PagedSlotScheduler

    arch, api = _serve_model(cfg_name, n_layers)
    sched = PagedSlotScheduler(api, None, arch, n_slots=2, max_len=64, block_size=16,
                               chunk=32)
    place = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=dev)
    params = jax.tree_util.tree_map(
        place, jax.eval_shape(lambda: unbox(api.init(jax.random.PRNGKey(0)))))
    n_phys = n_slots * max_len // 16 + 1
    pool = jax.tree_util.tree_map(
        place, jax.eval_shape(lambda: api.init_paged_cache(n_phys, 16)))
    return pool, _compile_programs(sched, params, pool, n_slots, max_len, dev)


def _pool_formats(compiled) -> tuple:
    """The layouts the program takes and returns the pool in (argument 1,
    and output 1 of ``(token or logits, pool)``)."""
    return compiled.input_formats[0][1], compiled.output_formats[1]


@pytest.mark.parametrize("cfg_name", sorted(SERVE_CELLS))
def test_paged_programs_compile_without_pool_copies(one_chip, cfg_name, monkeypatch):
    """Compiled for a described v5e at the cells' shapes (one layer, f32
    KV), neither the decode tick nor the prefill chunk copies a k/v pool
    leaf or layer, and both take and return the pool row-major: the TPU's
    own layout for rows of whole lane tiles, which the kernel reads in
    place through the scatter. Rows of unpadded 320 lanes (smollm) would
    be stored block-minor and copied around the kernel every call."""
    monkeypatch.setattr(ops, "_auto_interpret", bool)  # Mosaic, not the emulator
    n_slots, max_len = SERVE_CELLS[cfg_name]
    pool, programs = _abstract_programs(cfg_name, 1, n_slots, max_len, one_chip)
    for label, compiled in programs.items():
        assert not _pool_copies(compiled.as_text(), pool), label
        for formats in _pool_formats(compiled):
            assert {n: f.layout.major_to_minor for n, f in formats.items()} \
                == {n: (0, 1, 2, 3) for n in pool}, (label, formats)


@pytest.mark.parametrize("cfg_name", sorted(SERVE_CELLS))
def test_paged_programs_read_the_pool_in_place(cfg_name):
    """On the chip, at the cells' shapes (one layer, f32 KV): the pool the
    scheduler places is in the layout both programs take and return, the
    programs copy no k/v pool leaf or layer, and one request runs its
    prefill chunks and decode ticks through them. Prints each program's
    compiled peak memory; for smollm also at its published 32 layers with
    32 and 36 slots, where the layer scan's restacked pool still shows as
    whole-pool copies, printed, not asserted."""
    if jax.default_backend() != "tpu":
        pytest.skip("the placed pool's layout is the TPU runtime's; needs a TPU")
    import numpy as np

    from repro.nn.module import unbox
    from repro.serve.scheduler import PagedSlotScheduler, Request

    n_slots, max_len = SERVE_CELLS[cfg_name]
    arch, api = _serve_model(cfg_name, 1)
    params = unbox(api.init(jax.random.PRNGKey(0)))
    sched = PagedSlotScheduler(api, params, arch, n_slots=n_slots, max_len=max_len,
                               block_size=16, chunk=32)
    pool = sched.kv.cache
    placed = {n: leaf.format.layout for n, leaf in pool.items()}
    programs = _compile_programs(sched, params, pool, n_slots, max_len)
    for label, compiled in programs.items():
        print(_peak_line(f"{cfg_name} x1 {n_slots}x{max_len} {label}", compiled))
        assert not _pool_copies(compiled.as_text(), pool), label
        for formats in _pool_formats(compiled):
            assert {n: f.layout for n, f in formats.items()} == placed, (label, formats)
    prompt = np.arange(3 * 32 + 5, dtype=np.int32) % arch.vocab  # four chunks
    sched.submit(Request(rid=0, prompt=prompt, max_new_tokens=3))
    with jax.default_matmul_precision("highest"):
        (done,) = sched.run()
    assert len(done.output) == 3
    assert {n: leaf.format.layout for n, leaf in sched.kv.cache.items()} == placed
    if cfg_name == "smollm-360m":
        for slots in (32, 36):  # 36 is the most that fits one chip
            pool, programs = _abstract_programs(cfg_name, 32, slots, max_len)
            for label, compiled in programs.items():
                print(_peak_line(f"{cfg_name} x32 {slots}x{max_len} {label}", compiled))
                for line in _pool_copies(compiled.as_text(), pool):
                    print("  pool copy:", line)
