"""CAC-vs-baseline contraction wall time, CPU-relative (this container has no
TPU; numbers are meaningful as *ratios* between paths on the same host).

New-vs-old schedule A/B rows (DESIGN.md §2):
  * fused one-pass STE backward  vs the legacy two-call backward
  * m-folded single contraction  vs the per-m Python-loop sum
  * autotuned (heuristic) blocks vs the old fixed 256/256/512 blocks

Baseline-backend rows (DESIGN.md §3 — the registry's bnn / qnn8 kernel
routes, same interpret-mode caveats):
  * bnn XLA sign-matmul + qnn8 XLA int8 matmul vs the dense baseline
  * Pallas bnn forward / packed-bitplane forward / SignSTE backward pair
  * Pallas qnn8 int8+dequant forward

Pallas interpret-mode timing is excluded from *roofline* conclusions (it is
a Python emulator) but the fused-vs-two-call ratio is still meaningful
there: both sides pay the same per-call emulator overhead, so fewer kernel
launches + one mask recompute shows up directly.

Results are also written to BENCH_kernels.json at the repo root so future
PRs have a perf trajectory to regress against.
"""
from __future__ import annotations

import functools
import json
import os
from typing import Dict, List

import jax
import jax.numpy as jnp

from repro.core import bika as bika_core
from repro.core.backend import pack_signs
from repro.core.ste import sign_ste
from repro.kernels import autotune, ops
from .common import timed

# benchmarks/ ships inside the repo root, so dirname(dirname(__file__)) == root
_JSON_PATH = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                          "BENCH_kernels.json")


def _record(results: Dict[str, Dict], name: str, us: float, note: str,
            rows: List[str]) -> None:
    results[name] = {"us": round(us, 1), "note": note}
    rows.append(f"kernel/{name},{us:.1f},{note}")


def main(quick: bool = True) -> List[str]:
    m, k, n = (256, 1024, 512) if quick else (1024, 4096, 1024)
    key = jax.random.PRNGKey(0)
    kx, kw, kb = jax.random.split(key, 3)
    x = jax.random.normal(kx, (m, k))
    w = jax.random.normal(kw, (k, n)) * 0.05
    beta = jax.random.normal(kb, (k, n)) * 0.05
    tau, s = bika_core.to_hardware(w, beta)

    rows: List[str] = []
    results: Dict[str, Dict] = {}

    dense = jax.jit(lambda a, b: a @ b)
    bika_fused = jax.jit(bika_core.bika_matmul)
    bika_cvjp_g = jax.jit(jax.grad(lambda xx, ww, bb:
                                   bika_core.bika_matmul_cvjp(xx, ww, bb).sum(),
                                   argnums=(0, 1, 2)))
    bika_fused_g = jax.jit(jax.grad(lambda xx, ww, bb:
                                    bika_core.bika_matmul(xx, ww, bb).sum(),
                                    argnums=(0, 1, 2)))
    hw = jax.jit(lambda a, t, ss: bika_core.bika_matmul_hw(a, t, ss, clamp=False))

    t_dense = timed(dense, x, w)
    t_fused = timed(bika_fused, x, w, beta)
    t_hw = timed(hw, x, tau, s)
    t_gc = timed(bika_cvjp_g, x, w, beta)
    t_gf = timed(bika_fused_g, x, w, beta)

    _record(results, "dense_matmul", t_dense, f"1.00x baseline ({m}x{k}x{n})", rows)
    _record(results, "bika_fused_fwd", t_fused, f"{t_fused / t_dense:.2f}x dense", rows)
    _record(results, "bika_hw_fwd", t_hw, f"{t_hw / t_dense:.2f}x dense", rows)
    _record(results, "bika_grad_cvjp", t_gc,
            f"{t_gc / t_gf:.2f}x of fused-grad (bounded-memory backward)", rows)

    # -- baseline backends, XLA routes (what non-pallas impls lower) --
    bnn_xla = jax.jit(lambda a, b: sign_ste(a) @ sign_ste(b))
    xi8 = jnp.clip(jnp.round(x * 16.0), -127, 127).astype(jnp.int8)
    wi8 = jnp.clip(jnp.round(w * 64.0), -127, 127).astype(jnp.int8)
    qnn_xla = jax.jit(lambda a, b: jax.lax.dot(
        a, b, preferred_element_type=jnp.int32).astype(jnp.float32))
    t_bnn_x = timed(bnn_xla, x, w)
    t_qnn_x = timed(qnn_xla, xi8, wi8)
    _record(results, "bnn_xla_fwd", t_bnn_x, f"{t_bnn_x / t_dense:.2f}x dense "
            "(sign_ste matmul: the non-pallas train route)", rows)
    _record(results, "qnn_xla_int8_fwd", t_qnn_x, f"{t_qnn_x / t_dense:.2f}x "
            "dense (int8->int32 dot: the non-pallas serve route)", rows)

    # -- m-axis folding (XLA route): one contraction vs per-m Python sum.
    # The fold chunks the scan at the per-m term size (what linear_apply
    # does), so locality matches the old loop while issuing ONE op.
    mth = 4
    km = k // mth
    wm = jax.random.normal(kw, (mth, km, n)) * 0.05
    bm_ = jax.random.normal(kb, (mth, km, n)) * 0.05
    xm = x[:, :km]
    loop_fn = jax.jit(lambda xx, ww, bb: sum(
        bika_core.bika_matmul(xx, ww[j], bb[j]) for j in range(mth)))
    wf, bf = bika_core.fold_m_axis(wm, bm_)
    fold_fn = jax.jit(lambda xx, ww, bb: bika_core.bika_matmul(
        bika_core.tile_m_axis(xx, mth), ww, bb, chunk=km))
    t_loop = timed(loop_fn, xm, wm, bm_, iters=9)
    t_fold = timed(fold_fn, xm, wf, bf, iters=9)
    _record(results, f"m{mth}_xla_per_m_loop", t_loop,
            f"1.00x baseline (m={mth}, {m}x{km}x{n} per term)", rows)
    _record(results, f"m{mth}_xla_folded", t_fold,
            f"{t_fold / t_loop:.2f}x of per-m loop (chunked at K={km}; "
            "informational — XLA-CPU noise-bound; the kernel-route rows "
            "below carry the folding claim)", rows)

    if quick:
        # -- Pallas interpret-mode A/Bs (small shape; emulator-relative) --
        mi, ki, ni = 64, 128, 64
        xi, ti, si = x[:mi, :ki], tau[:ki, :ni], s[:ki, :ni]
        wi, bi = w[:ki, :ni], beta[:ki, :ni]
        gi = jnp.ones((mi, ni), jnp.float32)
        t_pal = timed(lambda: ops.cac_matmul(xi, ti, si), iters=2, warmup=1)
        _record(results, f"pallas_interpret_{mi}x{ki}x{ni}", t_pal,
                "interpret-mode (emulator; excluded from conclusions)", rows)

        vjp = lambda fused: jax.vjp(
            lambda *a: ops.cac_train_matmul(*a, fused_bwd=fused), xi, wi, bi
        )[1](gi)
        t_bwd2 = timed(lambda: vjp(False), iters=2, warmup=1)
        t_bwd1 = timed(lambda: vjp(True), iters=2, warmup=1)
        _record(results, "pallas_bwd_two_call", t_bwd2,
                "1.00x baseline (legacy dx-call + dw-call)", rows)
        _record(results, "pallas_bwd_fused_one_pass", t_bwd1,
                f"{t_bwd1 / t_bwd2:.2f}x of two-call (one mask recompute)", rows)

        # -- m-folding on the kernel route: m launches vs ONE folded launch --
        mthp = 4
        wmp = w[:ki, :ni].reshape(1, ki, ni).repeat(mthp, 0) * 0.9
        bmp = beta[:ki, :ni].reshape(1, ki, ni).repeat(mthp, 0) * 1.1
        wpf, bpf = bika_core.fold_m_axis(wmp, bmp)
        xpf = bika_core.tile_m_axis(xi, mthp)
        t_mloop = timed(lambda: sum(
            ops.cac_train_matmul(xi, wmp[j], bmp[j]) for j in range(mthp)),
            iters=2, warmup=1)
        t_mfold = timed(lambda: ops.cac_train_matmul(xpf, wpf, bpf),
                        iters=2, warmup=1)
        _record(results, f"pallas_m{mthp}_per_m_launches", t_mloop,
                f"1.00x baseline ({mthp} kernel launches)", rows)
        _record(results, f"pallas_m{mthp}_folded_one_launch", t_mfold,
                f"{t_mfold / t_mloop:.2f}x of per-m launches", rows)

        # -- autotuned blocks vs the old fixed 256/256/512 default, at a
        # decode-like long-K shape where the heuristic actually diverges
        # from the fixed config after clamping (fixed keeps bk=512, the
        # heuristic deepens to bk=1024: half the k-grid steps) --
        mb, kb2, nb = 32, 4096, 128
        xb = jax.random.normal(kx, (mb, kb2))
        tb = jax.random.normal(kw, (kb2, nb))
        sb = jnp.sign(jax.random.normal(kb, (kb2, nb)))
        bl = autotune.get_blocks(mb, kb2, nb, "hw_fwd", use_cache=False)
        fixed = autotune.get_blocks(mb, kb2, nb, "hw_fwd", use_cache=False,
                                    overrides=dict(block_m=256, block_n=256,
                                                   block_k=512))
        # -- registry baseline routes (bnn / qnn8), interpret-mode A/Bs --
        wbi = jnp.where(wi >= 0, 1, -1).astype(jnp.int8)
        wpk = pack_signs(wbi)
        t_bnnp = timed(lambda: ops.bnn_matmul(xi, wi), iters=2, warmup=1)
        t_bnnpk = timed(lambda: ops.bnn_matmul_packed(xi, wpk), iters=2,
                        warmup=1)
        _record(results, f"pallas_bnn_fwd_{mi}x{ki}x{ni}", t_bnnp,
                "1.00x baseline (sub-tiled sign-MXU forward)", rows)
        _record(results, "pallas_bnn_packed_fwd", t_bnnpk,
                f"{t_bnnpk / t_bnnp:.2f}x of unpacked (uint8 bitplanes "
                "unpacked per beat; 8x less weight HBM on TPU)", rows)
        bnn_vjp_p = lambda: jax.vjp(ops.bnn_train_matmul, xi, wi)[1](gi)
        t_bnnb = timed(bnn_vjp_p, iters=2, warmup=1)
        _record(results, "pallas_bnn_ste_bwd", t_bnnb,
                f"{t_bnnb / t_bnnp:.2f}x of pallas-bnn fwd (emulator-"
                "relative; masked dx+dw MXU pair ~= 2 contractions, no HBM "
                "mask tensors)", rows)
        xq8 = jnp.clip(jnp.round(xi * 16.0), -127, 127).astype(jnp.int8)
        wq8 = jnp.clip(jnp.round(wi * 64.0), -127, 127).astype(jnp.int8)
        wsc = jnp.abs(wi).max(axis=0, keepdims=True) / 127.0
        t_qnnp = timed(lambda: ops.qnn_matmul(xq8, wq8, wsc, 0.05), iters=2,
                       warmup=1)
        _record(results, f"pallas_qnn8_fwd_{mi}x{ki}x{ni}", t_qnnp,
                f"{t_qnnp / t_bnnp:.2f}x of pallas-bnn (int8 beats + fused "
                "dequant)", rows)

        # -- paged attention: fused block-walk kernel vs the XLA gather
        # oracle across a (n_slots, max_len, block_size) grid, fp32 and
        # int8 pools. Emulator-relative like every interpret-mode row; the
        # serving-level TPOT comparison lives in serving_bench.py.
        import numpy as _np

        from repro.kernels import ref as _ref
        from repro.models.base import PagedKVLayout
        hqa, hkva, da = 4, 2, 16
        gather_jit = jax.jit(functools.partial(_ref.paged_attention_ref, n_kv_heads=hkva))
        for ns, ml, bsz in ((2, 64, 8), (2, 128, 16), (4, 128, 16)):
            tt = ml // bsz
            npb = ns * tt + 1
            lanes = PagedKVLayout(1, npb, bsz, hkva, da).row_lanes
            kq = jax.random.split(jax.random.PRNGKey(ns * ml), 3)
            qa = jax.random.normal(kq[0], (ns, 1, hqa, da))
            tbl = jnp.asarray(_np.arange(ns * tt, dtype=_np.int32).reshape(ns, tt))
            qp = jnp.full((ns, 1), 3 * ml // 4 - 1, jnp.int32)
            for quant in (False, True):
                if quant:
                    ka = jax.random.randint(kq[1], (npb, bsz, lanes), -127, 128, jnp.int8)
                    va = jax.random.randint(kq[2], (npb, bsz, lanes), -127, 128, jnp.int8)
                    sc = jnp.full((npb, bsz, hkva), 0.01, jnp.float32)
                    scales = dict(k_scale=sc, v_scale=sc)
                else:
                    ka = jax.random.normal(kq[1], (npb, bsz, lanes))
                    va = jax.random.normal(kq[2], (npb, bsz, lanes))
                    scales = {}
                tag = f"s{ns}_L{ml}_b{bsz}" + ("_int8" if quant else "")
                t_g = timed(lambda: gather_jit(qa, ka, va, tbl, qp, **scales),
                            iters=2, warmup=1)
                t_f = timed(lambda: ops.paged_attention(qa, ka, va, tbl, qp,
                                                        n_kv_heads=hkva, **scales),
                            iters=2, warmup=1)
                _record(results, f"paged_gather_{tag}", t_g,
                        "1.00x baseline (XLA gather + full softmax)", rows)
                _record(results, f"paged_fused_{tag}", t_f,
                        f"{t_f / t_g:.2f}x of gather (block-walk online "
                        "softmax; emulator-relative)", rows)

        t_def = timed(lambda: ops.cac_matmul(xb, tb, sb, **fixed),
                      iters=2, warmup=1)
        t_tuned = timed(lambda: ops.cac_matmul(xb, tb, sb, **bl),
                        iters=2, warmup=1)
        distinct = bl != fixed
        _record(results, "pallas_blocks_fixed", t_def,
                f"1.00x baseline ({fixed['block_m']}/{fixed['block_n']}/"
                f"{fixed['block_k']} at {mb}x{kb2}x{nb})", rows)
        _record(results, "pallas_blocks_tuned", t_tuned,
                f"{t_tuned / t_def:.2f}x of fixed "
                f"({bl['block_m']}/{bl['block_n']}/{bl['block_k']})"
                + ("" if distinct else "; WARNING identical configs — vacuous"),
                rows)

    payload = {
        "meta": {
            "backend": jax.default_backend(),
            "quick": quick,
            "shape": [m, k, n],
            "units": "us_per_call_median",
        },
        "results": results,
    }
    try:
        with open(_JSON_PATH, "w") as fh:
            json.dump(payload, fh, indent=1, sort_keys=True)
            fh.write("\n")
        rows.append(f"bench_json,0.0,wrote {os.path.basename(_JSON_PATH)}")
    except OSError as e:
        rows.append(f"bench_json,0.0,SKIPPED ({e})")
    return rows


if __name__ == "__main__":
    print("\n".join(main()))
