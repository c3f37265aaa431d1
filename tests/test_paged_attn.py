"""Fused paged-attention kernel tests: kernel-vs-gather-oracle parity (fp32
and int8 pools, GQA, block_h sweeps, decode and chunked-prefill shapes), the
"paged_attn" autotune path (key format, heuristic clamping, override
validation, measured search persisting to the on-disk cache), engine-level
token parity of the fused route against the gather route and the dense
continuous oracle on every backend, the pinned quantized_kv+paged numeric
bound vs the fp dense oracle, and tp=2 serving through the sharded kernel."""
import json
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels import autotune, ops, ref
from repro.kernels.paged_attn import paged_attn_kernel_call
from repro.models import build_model
from repro.nn.module import unbox
from repro.serve.engine import Request, ServeEngine

KEY = jax.random.PRNGKey(11)


def _case(rng, b, c, hq, hkv, d, bs, t, quantized):
    """One synthetic paged-attention problem: shuffled physical pool, each
    row's table naming t random distinct blocks, in-range query positions."""
    n_phys = b * t + 3
    q = jnp.asarray(rng.normal(size=(b, c, hq, d)), jnp.float32)
    tables = jnp.asarray(rng.permutation(n_phys)[: b * t].reshape(b, t), jnp.int32)
    if c == 1:
        q_pos = jnp.asarray(rng.integers(0, t * bs, size=(b, 1)), jnp.int32)
    else:
        start = rng.integers(0, t * bs - c, size=(b,))
        q_pos = jnp.asarray(start[:, None] + np.arange(c)[None], jnp.int32)
    if quantized:
        k = jnp.asarray(rng.integers(-127, 128, size=(n_phys, bs, hkv, d)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, size=(n_phys, bs, hkv, d)), jnp.int8)
        ks = jnp.asarray(rng.uniform(1e-3, 2e-2, size=(n_phys, bs, hkv, 1)), jnp.float32)
        vs = jnp.asarray(rng.uniform(1e-3, 2e-2, size=(n_phys, bs, hkv, 1)), jnp.float32)
    else:
        k = jnp.asarray(rng.normal(size=(n_phys, bs, hkv, d)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(n_phys, bs, hkv, d)), jnp.float32)
        ks = vs = None
    return q, k, v, tables, q_pos, ks, vs


# ---------------------------------------------------------------------------
# kernel vs gather oracle
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "b,c,hq,hkv,d,bs,t,quantized,block_h",
    [
        (2, 1, 4, 4, 16, 8, 4, False, None),  # MHA decode
        (3, 1, 8, 2, 32, 16, 3, False, 1),    # GQA decode, block_h=1
        (2, 1, 8, 4, 16, 8, 5, True, 2),      # int8 decode, partial heads
        (2, 6, 4, 2, 16, 8, 4, False, None),  # chunked prefill
        (2, 5, 8, 4, 16, 8, 4, True, None),   # int8 chunked prefill
        (1, 1, 2, 2, 64, 16, 8, False, 2),    # long context
    ],
)
def test_kernel_matches_gather_oracle(b, c, hq, hkv, d, bs, t, quantized, block_h):
    """Online-softmax block walk == full-softmax gather oracle to float
    rounding, fp32 and int8 pools, decode (C=1) and chunk (C>1) shapes."""
    rng = np.random.default_rng(b * 100 + c * 10 + hq)
    q, k, v, tables, q_pos, ks, vs = _case(rng, b, c, hq, hkv, d, bs, t, quantized)
    out = paged_attn_kernel_call(q, k, v, tables, q_pos, k_scale=ks, v_scale=vs,
                                 block_h=block_h, interpret=True)
    want = ref.paged_attention_ref(q, k, v, tables, q_pos, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_kernel_block_h_sweep_identical():
    """Every legal block_h gives the same answer — the knob is perf-only."""
    rng = np.random.default_rng(0)
    q, k, v, tables, q_pos, _, _ = _case(rng, 2, 1, 8, 4, 16, 8, 4, False)
    outs = [np.asarray(paged_attn_kernel_call(q, k, v, tables, q_pos,
                                              block_h=bh, interpret=True))
            for bh in (1, 2, 4)]
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-6)
    np.testing.assert_allclose(outs[0], outs[2], atol=1e-6)


def test_kernel_partial_final_block_masked():
    """q_pos mid-block: positions past it contribute exactly nothing —
    poisoning them with huge values must not change the output."""
    rng = np.random.default_rng(1)
    q, k, v, tables, q_pos, _, _ = _case(rng, 1, 1, 2, 2, 16, 8, 3, False)
    q_pos = jnp.asarray([[11]], jnp.int32)  # mid block 1; block 2 fully dead
    out = paged_attn_kernel_call(q, k, v, tables, q_pos, interpret=True)
    kp = k.at[tables[0, 1], 4:].set(1e4).at[tables[0, 2]].set(1e4)
    vp = v.at[tables[0, 1], 4:].set(1e4).at[tables[0, 2]].set(1e4)
    out_p = paged_attn_kernel_call(q, kp, vp, tables, q_pos, interpret=True)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(out_p))


def test_ops_route_and_wrapper():
    """kernels/ops.py resolves "paged_attn" like the matmul routes and the
    wrapper matches the oracle with autotuned blocks."""
    assert ops.kernel_route("paged_attn") is ops.paged_attention
    rng = np.random.default_rng(2)
    q, k, v, tables, q_pos, ks, vs = _case(rng, 2, 1, 4, 2, 16, 8, 4, True)
    out = ops.paged_attention(q, k, v, tables, q_pos, k_scale=ks, v_scale=vs)
    want = ref.paged_attention_ref(q, k, v, tables, q_pos, k_scale=ks, v_scale=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# autotune path
# ---------------------------------------------------------------------------


def test_paged_autotune_key_heuristic_overrides():
    key = autotune.paged_attn_cache_key(4, 128, 16, 32, 2)
    assert key.endswith(":paged_attn:4x128x16x32x2")
    bl = autotune.heuristic_paged_blocks(4, 128, 16, 32, 6)
    assert 6 % bl["block_h"] == 0
    # overrides win but clamp to a divisor; unknown keys are rejected
    assert autotune.get_paged_blocks(4, 128, 16, 32, 6,
                                     overrides={"block_h": 5}) == {"block_h": 3}
    with pytest.raises(TypeError):
        autotune.get_paged_blocks(4, 128, 16, 32, 6, overrides={"block_q": 8})


def test_paged_measured_search_persists(tmp_path, monkeypatch):
    """measured_paged_blocks times the real kernel over block_h divisors and
    writes the winner into the same on-disk cache get_paged_blocks reads."""
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "at.json"))
    autotune.clear_cache()
    shape = dict(n_slots=2, max_len=32, block_size=8, hd=16, kv_heads=2)
    best = autotune.measured_paged_blocks(**shape, n_heads=4, iters=1, warmup=1)
    assert 2 % best["block_h"] == 0
    data = json.loads((tmp_path / "at.json").read_text())
    key = autotune.paged_attn_cache_key(**shape)
    assert data[key] == best
    assert autotune.get_paged_blocks(**shape) == best
    autotune.clear_cache()


# ---------------------------------------------------------------------------
# engine-level route parity: fused == gather == dense continuous
# ---------------------------------------------------------------------------


def _drain(eng, prompts, n_new):
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=n_new))
    return {r.rid: list(r.output) for r in eng.run()}


@pytest.mark.parametrize("mode", ["dense", "bika", "bnn", "qnn8"])
def test_paged_routes_token_identical(mode):
    """Fused block-walk route == gather route == dense continuous oracle,
    token for token, mixed prompt lengths, every backend."""
    arch = get_smoke("smollm-360m", compute_mode=mode, remat=False)
    if mode == "bika":
        arch = arch.replace(pack_signs=True)
    api_f = build_model(arch, phase="serve")
    params = unbox(api_f.init(KEY))
    rng = np.random.RandomState(6)
    prompts = [rng.randint(0, arch.vocab, size=int(rng.randint(3, 12)))
               .astype(np.int32) for _ in range(4)]

    outs = {}
    eng = ServeEngine(api_f, params, arch, max_len=32, engine="continuous",
                      n_slots=2)
    outs["dense"] = _drain(eng, prompts, 5)
    for route in ("fused", "gather"):
        arch_r = arch.replace(paged_attn_route=route)
        api = build_model(arch_r, phase="serve")
        eng = ServeEngine(api, params, arch_r, max_len=32, engine="paged",
                          n_slots=2, kv_block_size=8, prefill_chunk=8)
        outs[route] = _drain(eng, prompts, 5)
    assert outs["fused"] == outs["gather"] == outs["dense"], mode


def test_paged_byte_gauges_report(mode="dense"):
    """Satellite gauges: pool bytes, per-token bytes, in-use peak and the
    modeled decode HBM-bytes-per-token all populate; the fused route's
    decode figure is below the gather route's 3x-window model."""
    arch = get_smoke("smollm-360m", compute_mode=mode, remat=False)
    api = build_model(arch, phase="serve")
    params = unbox(api.init(KEY))
    rng = np.random.RandomState(7)
    prompts = [rng.randint(0, arch.vocab, size=6).astype(np.int32)
               for _ in range(3)]
    reads = {}
    for route in ("fused", "gather"):
        arch_r = arch.replace(paged_attn_route=route)
        api_r = build_model(arch_r, phase="serve")
        eng = ServeEngine(api_r, params, arch_r, max_len=32, engine="paged",
                          n_slots=2, kv_block_size=8, prefill_chunk=8)
        _drain(eng, prompts, 5)
        m = eng.metrics.summary()
        assert m["kv_pool_bytes"] > 0 and m["kv_bytes_per_token"] > 0
        assert m["kv_bytes_in_use_peak"] > 0
        assert m["decode_hbm_bytes_per_token"] > 0
        reads[route] = m["decode_hbm_bytes_per_token"]
    assert reads["fused"] < reads["gather"] / 2


def test_int8_pool_context_per_byte():
    """The int8 pool's bytes-per-token is ~4x smaller than the fp32 pool's
    (int8 k+v payload + one f32 scale per position-head vs f32 payload):
    the same device bytes hold ~4x the context."""
    arch = get_smoke("smollm-360m", compute_mode="dense", remat=False)
    api = build_model(arch, phase="serve")
    params = unbox(api.init(KEY))
    bpt = {}
    for quant in (False, True):
        eng = ServeEngine(api, params, arch, max_len=32, engine="paged",
                          n_slots=2, kv_block_size=8, prefill_chunk=8,
                          quantized_kv=quant)
        bpt[quant] = eng.scheduler.kv.bytes_per_token
    ratio = bpt[False] / bpt[True]
    # f32: 2*h*d*4 B/token; int8: 2*h*(d+4) B/token -> 4d/(d+4) = 3.76 @ d=32
    assert ratio == pytest.approx(4 * arch.hd / (arch.hd + 4), rel=1e-6)
    assert ratio > 3.5


# ---------------------------------------------------------------------------
# quantized_kv + paged: pinned numeric bound vs the fp dense oracle
# ---------------------------------------------------------------------------


def test_quantized_paged_bound_vs_dense_oracle():
    """The documented non-parity mode, now pinned: int8-pool paged serving
    (fused route) stays within a stated logit bound of the fp dense oracle
    and greedy-decodes the same tokens on the smoke config."""
    arch = get_smoke("smollm-360m", compute_mode="dense", remat=False)
    api = build_model(arch, phase="serve")
    params = unbox(api.init(KEY))
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, arch.vocab, size=9).astype(np.int32)
    n_new, max_len = 6, 32

    # fp dense oracle: whole-prompt prefill + per-step logits
    logits, cache = api.prefill(params, {"tokens": jnp.asarray(prompt)[None]},
                                max_len=max_len)
    ref_logits = [np.asarray(logits)[0, -1]]
    tok = int(np.argmax(ref_logits[-1]))
    ref_toks, pos = [tok], len(prompt)
    for _ in range(n_new - 1):
        logits, cache = api.decode_step(params, jnp.asarray([[tok]]), cache,
                                        jnp.asarray([pos]))
        ref_logits.append(np.asarray(logits)[0, -1])
        tok = int(np.argmax(ref_logits[-1]))
        ref_toks.append(tok)
        pos += 1

    # int8 paged: chunked prefill + fused block-walk decode, one slot
    bs = 8
    t = max_len // bs
    cache = api.init_cache(t + 1, bs, quantized=True)
    tables = jnp.asarray(np.arange(t, dtype=np.int32))[None]
    chunk = 8
    padded = np.zeros(((len(prompt) + chunk - 1) // chunk) * chunk, np.int32)
    padded[: len(prompt)] = prompt
    for ci in range(len(padded) // chunk):
        toks = jnp.asarray(padded[ci * chunk:(ci + 1) * chunk])[None]
        last = jnp.asarray([(len(prompt) - 1) % chunk])
        logits, cache = api.prefill_chunk(params, toks, cache, tables,
                                          jnp.asarray([ci * chunk]), last)
    got_logits = [np.asarray(logits)[0, -1]]
    tok = int(np.argmax(got_logits[-1]))
    got_toks, pos = [tok], len(prompt)
    for _ in range(n_new - 1):
        logits, cache = api.decode_paged(params, jnp.asarray([[tok]]), cache,
                                         jnp.asarray([pos]), tables)
        got_logits.append(np.asarray(logits)[0, -1])
        tok = int(np.argmax(got_logits[-1]))
        got_toks.append(tok)
        pos += 1

    assert got_toks == ref_toks
    err = max(float(np.max(np.abs(g - r)))
              for g, r in zip(got_logits, ref_logits))
    # int8 KV round-trip bound on this config; update deliberately if the
    # quantizer changes, never to paper over a regression
    assert err < 0.25, err


# ---------------------------------------------------------------------------
# tp=2: fused route shards over the model axis, tokens unchanged
# ---------------------------------------------------------------------------


def _run_sub(body: str):
    code = ("""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, numpy as np, jax.numpy as jnp
from jax.sharding import Mesh
""" + textwrap.dedent(body))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                       env={**__import__("os").environ, "PYTHONPATH": "src"},
                       timeout=600)
    assert r.returncode == 0, f"STDOUT:\n{r.stdout}\nSTDERR:\n{r.stderr[-3000:]}"
    return r.stdout


@pytest.mark.slow
def test_fused_route_tp2_token_identical():
    """Fused route on a (4, 2) data x model mesh (kv_heads=2 divides tp=2,
    so the kernel runs under shard_map) == 1-device gather route, token for
    token, dense and qnn8."""
    out = _run_sub("""
    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import Request, ServeEngine

    mesh = Mesh(np.asarray(jax.devices()).reshape(4, 2), ("data", "model"))

    def run(mode, mesh_, route):
        arch = get_smoke("smollm-360m", compute_mode=mode, remat=False).replace(
            n_heads=4, n_kv_heads=2, head_dim=24, paged_attn_route=route)
        api = build_model(arch, phase="serve")
        params = unbox(api.init(jax.random.PRNGKey(0)))
        eng = ServeEngine(api, params, arch, max_len=32, engine="paged",
                          n_slots=2, kv_block_size=8, prefill_chunk=8,
                          mesh=mesh_)
        rng = np.random.RandomState(0)
        for i in range(4):
            plen = int(rng.randint(3, 12))
            eng.submit(Request(rid=i, prompt=rng.randint(0, arch.vocab, plen)
                               .astype(np.int32), max_new_tokens=5))
        return {r.rid: list(r.output) for r in eng.run()}

    for mode in ("dense", "qnn8"):
        ref = run(mode, None, "gather")
        got = run(mode, mesh, "fused")
        assert ref == got, (mode, ref, got)
        print(mode, "OK")
    print("FUSED_TP2_OK")
    """)
    assert "FUSED_TP2_OK" in out


def test_tp_route_choice_is_logged_once_per_shape(caplog, monkeypatch):
    """Under a model-axis mesh the kernel runs per head shard when the head
    counts divide the axis, and otherwise falls back to the XLA oracle —
    visibly: one warning per (route, shape), and ``tp_dispatch_log`` names
    the route each call took."""
    # the dispatch log is process-global: start from an empty one so that
    # an earlier trace of the same shapes cannot swallow the warning
    monkeypatch.setattr(ops, "_TP_DISPATCH", {})
    mesh = jax.sharding.AbstractMesh((2,), ("model",))
    f32, i32 = jnp.float32, jnp.int32
    S = jax.ShapeDtypeStruct

    def trace(hq, hkv):
        args = (S((2, 1, hq, 16), f32), S((5, 8, hkv, 16), f32),
                S((5, 8, hkv, 16), f32), S((2, 2), i32), S((2, 1), i32))
        with jax.sharding.use_abstract_mesh(mesh):
            out = jax.eval_shape(ops.paged_attention, *args)
        assert out.shape == (2, 1, hq, 16)
        return ("paged_attn", (args[0].shape, args[1].shape, args[3].shape))

    with caplog.at_level("WARNING", logger="repro.kernels.ops"):
        odd = trace(3, 1)  # 3q/1kv heads do not divide model=2
        trace(3, 1)
        even = trace(4, 2)
    log = ops.tp_dispatch_log()
    assert log[odd] == "xla_fallback" and log[even] == "shard_map"
    warned = [r for r in caplog.records if "runs the XLA reference" in r.getMessage()]
    assert len(warned) == 1
