#!/usr/bin/env python3
"""Smoke test of the serving path on a TPU, at the full smollm-360m widths.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # only the tensor-parallel mesh phase

One chip, in one process:

(a) device check: JAX must see a TPU (there is no CPU fallback);
(b) kernel parity: every serve route of ``kernels.ops.KERNEL_ROUTES``
    (``cac_hw``, ``bnn``, ``bnn_packed``, ``qnn8``, ``paged_attn`` for decode
    and chunked prefill with fp and int8 KV) at the smollm-360m serve shapes,
    compared on the chip with its ``kernels/ref.py`` oracle;
(c) end to end: the paged ``ServeEngine`` (fused paged attention, the
    default) serves 4 requests on 4 slots in dense, bika, bnn and qnn8 from
    random weights converted train -> serve; every request must complete
    with its full token count, and the prefill logits must agree with the
    ``paged_attn_route="gather"`` programs (for bika on the configuration
    cut to one layer: see CHAOTIC_NOTE). bika is served once more with
    ``bika_impl="pallas"`` (the CAC kernel inside the 32-layer program),
    whose tokens and logits must agree with the default XLA route.

``--chips 4`` serves the same requests in dense and bika on a (1, 4) data x
model mesh, in the same process as a one-device run of them with the
attention the mesh runs (the gather route: see ``tp_mesh_phase``), checks
the 32-layer logits against it, and prints each device's ``bytes_in_use``
and the tensor-parallel route each kernel call took. bika's Pallas
projection route is checked on the mesh at one layer.

Every failure raises, so the exit code is non-zero. Earlier lines carry each
phase's wall time, its compile seconds per program, and the device's
``peak_bytes_in_use``; these are smoke wall times, not benchmark results.
The last line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))

ARCH = "smollm-360m"
SEED = 0  # random weights and prompts
MODES = ("dense", "bika", "bnn", "qnn8")
N_REQUESTS, N_SLOTS, MAX_LEN, NEW_TOKENS = 4, 4, 512, 16
PROMPT_LENS = (16, 96)  # inclusive range of prompt lengths
BLOCK_SIZE, CHUNK = 16, 32
DECODE_M, PREFILL_M = N_SLOTS, CHUNK  # matmul M of a decode tick / a chunk
# (K, N) of the smollm-360m projections: q and o (960 x 960), k and v
# (960 x 320), gate and up (960 x 2560), down (2560 x 960)
PROJ_KN = ((960, 960), (960, 320), (960, 2560), (2560, 960))

# Tolerances. Matmul routes sum integers (or int8 products) in f32 well below
# 2^24, so kernel and oracle must agree exactly (qnn8: up to the one f32
# rounding of the dequant scale). Attention is compared with an oracle run at
# "highest" precision: at the default precision the kernel's f32 dots run as
# bf16 MXU passes (about 3 significant digits), at "highest" as f32; a wrong
# mask, block or head mapping moves outputs by O(1) either way.
QNN_RTOL = 1e-6
ATTN_ATOL = 2e-2  # kernel at default precision
ATTN_ATOL_F32 = 1e-4  # kernel at "highest" precision
# Logits of two 32-layer programs, relative to the largest logit. They are
# compared with every matmul at "highest" precision: the quantized modes
# round activations onto an integer grid in every projection, so bf16-pass
# noise in one route flips roundings and decorrelates the two programs'
# logits, which says nothing about whether the routes compute the same
# function. The serving runs themselves use the default precision.
ROUTE_LOGIT_RTOL = 1e-3  # fused vs gather attention (dense, bnn)
IMPL_LOGIT_RTOL = 1e-3  # bika Pallas vs XLA projections: identical integer sums
TP_LOGIT_RTOL = 1e-3  # (1, 4) mesh vs one device, same attention
# qnn8 fused vs gather: qnn8 rounds its inputs onto a fixed grid too, and
# its 32-layer logits moved by 1.38e-2 on a v5e (0.044 of 3.19; exactly 0
# at one layer: CHAOTIC_NOTE). A wrong mask, block or head mapping moves
# them by O(max |logit|).
QNN8_ROUTE_LOGIT_RTOL = 5e-2
BIKA_1L_LOGIT_RTOL = 2e-2  # bika, fused vs gather, one layer
# CHAOTIC_NOTE: every bika projection rounds its input onto a fixed integer
# grid (step 0.05). Two attention routes that differ by one f32 ulp flip a
# few roundings; with 32 layers of random weights each flip triggers more,
# and the last-token logits decorrelate (measured on a v5e at "highest"
# precision: fused vs gather max |diff| 0.86 of max |logit| 2.84, while
# dense agrees to 1.5e-6; PERF.md has the sweep over depth). So bika's
# attention routes are compared on the configuration cut to one layer.


class Phases:
    """Wall time, compiles and device memory of each phase, printed as it
    ends. Compile seconds come from JAX's backend-compile events."""

    def __init__(self, device):
        import jax

        self.device = device
        self.compiles = []
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, secs, fun_name="?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles.append((fun_name, secs))

    def run(self, name, fn, *args):
        n0, t0 = len(self.compiles), time.perf_counter()
        out = fn(*args)
        wall = time.perf_counter() - t0
        comp = self.compiles[n0:]
        peak = (self.device.memory_stats() or {}).get("peak_bytes_in_use")
        slow = ", ".join(f"{f}={s:.2f}s" for f, s in comp if s >= 0.5)
        print(f"[{name}] wall={wall:.2f}s compiles={len(comp)} "
              f"compile_s={sum(s for _, s in comp):.2f} "
              f"peak_bytes_in_use={peak} (smoke wall time, not a benchmark)")
        if slow:
            print(f"[{name}] programs compiled >= 0.5 s: {slow}")
        return out


def _check(name, ok, detail):
    print(f"  {name}: {detail} -> {'ok' if ok else 'FAIL'}")
    if not ok:
        raise AssertionError(f"{name}: {detail}")


# ---------------------------------------------------------------------------
# (b) kernel parity
# ---------------------------------------------------------------------------


def kernel_parity():
    import jax
    import jax.numpy as jnp

    from repro.core.backend import pack_signs
    from repro.kernels import ops, ref
    from repro.models.base import PagedKVLayout

    rng = np.random.RandomState(SEED)
    f32 = np.float32

    def exact(name, got, want):
        err = float(np.max(np.abs(np.asarray(got) - np.asarray(want))))
        _check(name, err == 0.0, f"max |kernel - ref| = {err} (exact)")

    for m in (DECODE_M, PREFILL_M):
        for k, n in PROJ_KN:
            shape = f"M{m} K{k} N{n}"
            x_int = rng.randint(-128, 128, (m, k)).astype(f32)
            tau = rng.randint(-128, 128, (k, n)).astype(f32)
            s = rng.choice([-1.0, 1.0], (k, n)).astype(f32)
            exact(f"cac_hw {shape}", jax.jit(ops.cac_matmul)(x_int, tau, s),
                  jax.jit(ref.cac_matmul_ref)(x_int, tau, s))
            x = rng.randn(m, k).astype(f32)
            w = rng.randn(k, n).astype(f32)
            want = jax.jit(ref.bnn_matmul_ref)(x, w)
            exact(f"bnn {shape}", jax.jit(ops.bnn_matmul)(x, w), want)
            wp = pack_signs(jnp.where(w >= 0, 1, -1).astype(jnp.int8))
            exact(f"bnn_packed {shape}", jax.jit(ops.bnn_matmul_packed)(x, wp), want)
            xi = rng.randint(-128, 128, (m, k)).astype(np.int8)
            wi = rng.randint(-128, 128, (k, n)).astype(np.int8)
            ws = rng.uniform(1e-3, 1e-2, (1, n)).astype(f32)
            got = jax.jit(lambda a, b, c: ops.qnn_matmul(a, b, c, 0.05))(xi, wi, ws)
            want = jax.jit(lambda a, b, c: ref.qnn_matmul_ref(a, b, 0.05, c))(xi, wi, ws)
            rel = float(np.max(np.abs(np.asarray(got) - np.asarray(want))
                               / (np.abs(np.asarray(want)) + 1e-30)))
            _check(f"qnn8 {shape}", rel <= QNN_RTOL,
                   f"max rel err = {rel:.3g} (rtol {QNN_RTOL})")

    hq, hkv, d = 15, 5, 64
    t = MAX_LEN // BLOCK_SIZE
    n_phys = N_SLOTS * t + 1
    lanes = PagedKVLayout(1, n_phys, BLOCK_SIZE, hkv, d).row_lanes
    for label, b, c in (("decode", N_SLOTS, 1), ("chunk", 1, CHUNK)):
        tables = np.stack([rng.permutation(n_phys)[:t] for _ in range(b)]).astype(np.int32)
        if c == 1:
            q_pos = rng.randint(BLOCK_SIZE, MAX_LEN, (b, 1)).astype(np.int32)
        else:  # the third chunk of a prompt
            q_pos = (2 * CHUNK + np.arange(c, dtype=np.int32))[None].repeat(b, 0)
        q = rng.randn(b, c, hq, d).astype(f32)
        for quant in (False, True):
            if quant:
                k = rng.randint(-127, 128, (n_phys, BLOCK_SIZE, lanes)).astype(np.int8)
                v = rng.randint(-127, 128, (n_phys, BLOCK_SIZE, lanes)).astype(np.int8)
                ks = rng.uniform(0.005, 0.02, (n_phys, BLOCK_SIZE, hkv)).astype(f32)
                vs = rng.uniform(0.005, 0.02, (n_phys, BLOCK_SIZE, hkv)).astype(f32)
                scales = (ks, vs)
            else:
                k = rng.randn(n_phys, BLOCK_SIZE, lanes).astype(f32)
                v = rng.randn(n_phys, BLOCK_SIZE, lanes).astype(f32)
                scales = ()
            attn = lambda *a: ops.paged_attention(
                *a[:5], n_kv_heads=hkv, k_scale=a[5] if quant else None,
                v_scale=a[6] if quant else None)
            got = jax.jit(attn)(q, k, v, tables, q_pos, *scales)
            with jax.default_matmul_precision("highest"):
                got_f32 = jax.jit(attn)(q, k, v, tables, q_pos, *scales)
                want = jax.jit(lambda *a: ref.paged_attention_ref(*a, n_kv_heads=hkv))(
                    q, k, v, tables, q_pos, *scales)
            name = f"paged_attn {label} B{b} C{c} {'int8' if quant else 'fp'} KV"
            for prec, out, tol in (("default", got, ATTN_ATOL),
                                   ("highest", got_f32, ATTN_ATOL_F32)):
                err = float(np.max(np.abs(np.asarray(out) - np.asarray(want))))
                _check(f"{name} ({prec} precision)", err <= tol,
                       f"max |kernel - ref| = {err:.3g} (atol {tol})")


# ---------------------------------------------------------------------------
# (c) end to end through the paged ServeEngine
# ---------------------------------------------------------------------------


def make_prompts(vocab):
    rng = np.random.RandomState(SEED + 1)
    lens = rng.randint(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    return [rng.randint(0, vocab, int(n)).astype(np.int32) for n in lens]


def serve_arch(mode, **over):
    from repro.configs import get_config

    arch = get_config(ARCH, compute_mode=mode, remat=False)
    if mode == "bika":
        arch = arch.replace(pack_signs=True)  # as launch.serve serves bika
    return arch.replace(**over)


def serve_params(arch):
    """Random train-phase weights from ``SEED``, converted to the serve form
    through the backend registry (the train -> deploy path)."""
    import jax

    from repro.models import build_model
    from repro.nn.module import unbox
    from repro.serve.engine import serve_params_from_train

    tparams = unbox(build_model(arch, phase="train").init(jax.random.PRNGKey(SEED)))
    return jax.block_until_ready(serve_params_from_train(tparams, arch.linear_spec()))


def build_engine(arch, params, mesh=None):
    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    api = build_model(arch, phase="serve")
    return ServeEngine(api, params, arch, engine="paged", batch_size=N_SLOTS,
                       n_slots=N_SLOTS, max_len=MAX_LEN, kv_block_size=BLOCK_SIZE,
                       prefill_chunk=CHUNK, mesh=mesh)


def serve_requests(eng, prompts):
    """Serve every prompt to completion; returns the outputs by request id."""
    from repro.serve.engine import Request

    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new_tokens=NEW_TOKENS))
    done = sorted(eng.run(), key=lambda r: r.rid)
    counts = [len(r.output) for r in done]
    _check(f"{eng.arch.compute_mode} requests complete", len(done) == len(prompts)
           and all(n == NEW_TOKENS for n in counts),
           f"{len(done)}/{len(prompts)} done, tokens {counts} (want {NEW_TOKENS} each)")
    return [np.asarray(r.output) for r in done]


def prefill_logits(sched, prompts, api=None):
    """Last-token logits of each prompt from the scheduler's chunked prefill
    program (or, with ``api``, the same program built around another model
    build, e.g. the gather route), on a fresh pool shaped like the
    scheduler's, with every matmul at "highest" precision (see the logit
    tolerances above)."""
    import jax

    from repro.serve.compile_cache import ChunkedPrefill
    from repro.serve.paged_kv import PagedKVManager

    api = api or sched.api
    kv = PagedKVManager(api, n_slots=sched.n_slots, max_len=sched.max_len,
                        block_size=sched.block_size, prefix_cache=False,
                        mesh=sched.mesh, rules=sched.rules)
    prefill = sched.prefill if api is sched.api else ChunkedPrefill(
        api, chunk=sched.chunk, max_len=sched.max_len)
    out = []
    with jax.default_matmul_precision("highest"):
        for p in prompts:
            slot = kv.alloc_slot()
            cached = kv.try_admit(slot, p, budget=1, chunk=sched.chunk)
            logits, kv.cache, _ = prefill(sched.params, kv.cache, kv.tables[slot],
                                          p, cached)
            out.append(np.asarray(logits, np.float32).reshape(-1))
            kv.free_slot(slot)
    return np.stack(out)


def compare_logits(name, got, want, rtol):
    scale = float(np.max(np.abs(want)))
    err = float(np.max(np.abs(got - want)))
    same_argmax = int(np.sum(np.argmax(got, -1) == np.argmax(want, -1)))
    _check(name, bool(np.all(np.isfinite(got))) and err <= rtol * scale,
           f"max |diff| = {err:.4g}, max |logit| = {scale:.4g}; argmax agrees "
           f"on {same_argmax}/{len(want)} prompts (rtol {rtol})")


def gather_logits(sched, arch, prompts):
    from repro.models import build_model

    api = build_model(arch.replace(paged_attn_route="gather"), phase="serve")
    return prefill_logits(sched, prompts, api=api)


ROUTE_RTOL = {"dense": ROUTE_LOGIT_RTOL, "bnn": ROUTE_LOGIT_RTOL,
              "qnn8": QNN8_ROUTE_LOGIT_RTOL}


def serve_mode(mode, prompts):
    arch = serve_arch(mode)
    params = serve_params(arch)
    eng = build_engine(arch, params)
    outs = serve_requests(eng, prompts)
    m = eng.metrics.summary()
    print(f"  {mode}: goodput={m['goodput_tok_s']:.1f} tok/s prefill "
          f"compiles={m['prefill_compiles']} first tokens "
          f"{[o[:4].tolist() for o in outs]} (smoke wall time, not a benchmark)")
    fused = prefill_logits(eng.scheduler, prompts)
    if mode != "bika":
        compare_logits(f"{mode} fused vs gather prefill logits, {arch.n_layers} layers",
                       fused, gather_logits(eng.scheduler, arch, prompts),
                       ROUTE_RTOL[mode])
        return
    peng = build_engine(arch.replace(bika_impl="pallas"), params)
    pouts = serve_requests(peng, prompts)
    same = sum(int(np.array_equal(a, b)) for a, b in zip(pouts, outs))
    _check("bika pallas vs xla served tokens", same == len(outs),
           f"{same}/{len(outs)} requests token-identical")
    compare_logits(f"bika pallas vs xla prefill logits, {arch.n_layers} layers",
                   prefill_logits(peng.scheduler, prompts), fused, IMPL_LOGIT_RTOL)
    # the attention routes on the configuration cut to one layer, where a
    # rounding flip cannot cascade (CHAOTIC_NOTE)
    arch1 = arch.replace(n_layers=1)
    eng1 = build_engine(arch1, serve_params(arch1))
    compare_logits("bika fused vs gather prefill logits, 1 layer",
                   prefill_logits(eng1.scheduler, prompts),
                   gather_logits(eng1.scheduler, arch1, prompts), BIKA_1L_LOGIT_RTOL)


# ---------------------------------------------------------------------------
# --chips 4: the tensor-parallel mesh path
# ---------------------------------------------------------------------------


def tp_mesh_phase(prompts):
    """dense and bika on a (1, 4) mesh against one device. On the mesh,
    smollm-360m's 15q/5kv heads do not divide the model axis, so attention
    runs the XLA oracle; the one-device engine is built with
    ``paged_attn_route="gather"``, the same attention, so the 32-layer
    logits compare like for like. bika's Pallas projection route
    (column-parallel CAC kernel per shard) is checked at one layer only: on
    a v5e that mesh program is not bit-identical to one device (7.2e-7 at
    one layer), and from two layers on the difference cascades
    (CHAOTIC_NOTE; the sweep over depth is in PERF.md)."""
    import gc

    import jax

    from repro.distributed.meshes import make_mesh
    from repro.kernels import ops

    mesh = make_mesh((1, 4), ("data", "model"))
    for mode, over in (("dense", {}), ("bika", {}),
                       ("bika", {"bika_impl": "pallas", "n_layers": 1})):
        arch = serve_arch(mode, **over)
        label = f"{mode}/{arch.bika_impl}" if mode == "bika" else mode
        params = serve_params(arch)
        one = build_engine(arch.replace(paged_attn_route="gather"), params)
        one_outs = serve_requests(one, prompts)
        one_logits = prefill_logits(one.scheduler, prompts)
        del one
        gc.collect()
        tp = build_engine(arch, params, mesh=mesh)
        del params
        gc.collect()
        in_use = [(d.memory_stats() or {}).get("bytes_in_use")
                  for d in mesh.devices.flat]
        print(f"  {label}: bytes_in_use per device with only the mesh engine "
              f"live (params + KV pool): {in_use}")
        tp_outs = serve_requests(tp, prompts)
        same = sum(int(np.array_equal(a, b)) for a, b in zip(tp_outs, one_outs))
        print(f"  {label}: {same}/{len(one_outs)} requests token-identical to one "
              "device (default precision; not checked)")
        compare_logits(f"{label} (1, 4) mesh vs one device (gather attention) "
                       f"prefill logits, {arch.n_layers} layer(s)",
                       prefill_logits(tp.scheduler, prompts), one_logits,
                       TP_LOGIT_RTOL)
        del tp
        gc.collect()
        jax.clear_caches()
    print("  tensor-parallel route taken per kernel call (route, operand shapes):")
    for (route, shapes), taken in sorted(ops.tp_dispatch_log().items(), key=str):
        print(f"    {route} {shapes}: {taken}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: kernel parity + four modes served on one chip; "
                         "4: only the (1, 4) tensor-parallel mesh phase")
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU — JAX sees {len(devices)} "
                         f"{dev.platform} device(s); this smoke runs on a TPU only")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} but JAX sees "
                         f"{len(devices)} device(s)")
    print(f"[device] platform={dev.platform} kind={dev.device_kind} "
          f"count={len(devices)}")

    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.configs import get_config
    from repro.launch.cache import use_persistent_compile_cache

    print(f"[setup] persistent compile cache: {use_persistent_compile_cache()}")
    prompts = make_prompts(get_config(ARCH).vocab)
    print(f"[setup] {ARCH}: {N_REQUESTS} requests, prompt lengths "
          f"{[len(p) for p in prompts]}, {NEW_TOKENS} new tokens, {N_SLOTS} "
          f"slots, max_len {MAX_LEN}, block {BLOCK_SIZE}, chunk {CHUNK}")
    phases = Phases(dev)
    if args.chips == 4:
        phases.run("tp mesh (1, 4)", tp_mesh_phase, prompts)
    else:
        phases.run("kernel parity", kernel_parity)
        for mode in MODES:
            phases.run(f"serve {mode}", serve_mode, mode, prompts)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
