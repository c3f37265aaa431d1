"""Pass 2 — Pallas kernel contract verifier (DESIGN.md §9).

For every route in ``kernels.ops.KERNEL_ROUTES`` and every architecture in
the config zoo (``repro.configs.ARCH_NAMES``) this pass *abstractly*
evaluates the kernel wrapper (``jax.eval_shape`` — no kernel execution, so
it runs on CPU CI in seconds) and re-derives the block/grid arithmetic the
wrapper would use, checking:

  KCV001  route/metadata coverage — every KERNEL_ROUTES entry has contract
          metadata here and vice versa (a new route cannot ship unchecked)
  KCV002  block legality — padded dims divisible by their blocks, grid
          covers the padded problem exactly, ``block_k_sub`` divides
          ``block_k``, packed-bitplane K beats slice whole bytes
  KCV003  index-map bounds — the last grid step's block starts inside the
          padded operand on every axis
  KCV004  VMEM footprint — the route's resident block working set (operand
          windows + output window + the broadcast sub-tile) fits the
          per-kernel budget shared with hwsim (``hwsim.resource.
          KERNEL_VMEM_BUDGET``)
  KCV005  abstract-eval contract — ``jax.eval_shape`` of the real wrapper
          returns the declared output shape/dtype for the route's input
          dtype signature (int8/uint8 on the quantized paths; no silent
          upcast of the output)
  KCV006  autotune-key consistency — ``autotune.cache_key`` round-trips
          through ``parse_cache_key`` to the same (path, shape), and every
          registered backend's ``autotune_key`` agrees with its
          ``kernel_route``/``autotune_path``
  KCV007  on-disk autotune cache hygiene — entries the loader rejected
          (see ``autotune.validate_cache_entry``) are surfaced as findings
          instead of silently dropped

The JSON payload carries one entry per (route, arch) — blocks, grid and the
VMEM estimate — which is the coverage artifact CI uploads.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs import ARCH_NAMES, get_config
from repro.hwsim.resource import KERNEL_VMEM_BUDGET
from repro.kernels import autotune, ops
from repro.kernels.bnn_matmul import PACKED_WINDOW

from .report import Finding, Report

__all__ = [
    "ROUTE_INFO",
    "check_matmul_contract",
    "check_paged_attn_contract",
    "config_gemms",
    "matmul_vmem_bytes",
    "paged_attn_vmem_bytes",
    "run",
]

PASS = "kernel_contracts"

# Representative serving/training row counts: decode ticks see a slot batch,
# prefill/training see (batch x seq) rows. VMEM pressure is block-dominated,
# so these only matter through the block_m clamp.
M_DECODE = 8
M_PREFILL = 2048

# Per-route contract metadata. `path` is the kernels.autotune heuristic/cache
# path the wrapper resolves blocks under; `in_dtypes` the wrapper's operand
# signature for the abstract-eval check.
ROUTE_INFO: Dict[str, Dict] = {
    "cac_hw": dict(kind="matmul", path="hw_fwd", phase="serve"),
    "cac_train": dict(kind="matmul", path="train_fwd", phase="train",
                      bwd_path="train_bwd"),
    "bnn": dict(kind="matmul", path="bnn", phase="both"),
    "bnn_packed": dict(kind="matmul", path="bnn", phase="serve", packed=True),
    "bnn_train": dict(kind="matmul", path="bnn", phase="train",
                      bwd_path="bnn_bwd"),
    "qnn8": dict(kind="matmul", path="qnn8", phase="serve", int8=True),
    "paged_attn": dict(kind="attention", path="paged_attn", phase="serve"),
}

_F32 = jnp.dtype(jnp.float32)


def _round_up(v: int, b: int) -> int:
    return -(-v // b) * b


def config_gemms(cfg) -> Dict[str, Tuple[int, int]]:
    """The (K, N) projection shapes a config's linear layers issue."""
    hd = cfg.hd
    gemms = {
        "attn_qkv": (cfg.d_model, (cfg.n_heads + 2 * cfg.n_kv_heads) * hd),
        "attn_out": (cfg.n_heads * hd, cfg.d_model),
        "mlp_in": (cfg.d_model, cfg.d_ff * (2 if cfg.gated_mlp else 1)),
        "mlp_out": (cfg.d_ff, cfg.d_model),
        "lm_head": (cfg.d_model, cfg.padded_vocab),
    }
    # degenerate layers (e.g. xlstm's d_ff=0: mLSTM expansion, no MLP) are
    # never lowered — skip, don't "check" a 0-sized GEMM
    return {name: (k, n) for name, (k, n) in gemms.items() if k and n}


# ---------------------------------------------------------------------------
# Block/grid arithmetic (mirrors kernels/ops.py padding + autotune clamp)
# ---------------------------------------------------------------------------


def _resolve(route: str, m: int, k: int, n: int,
             blocks: Optional[Dict[str, int]] = None,
             path: Optional[str] = None) -> Dict[str, int]:
    info = ROUTE_INFO[route]
    path = path or info["path"]
    bl = autotune.get_blocks(m, k, n, path, overrides=blocks or None)
    bm, bn, bk = bl["block_m"], bl["block_n"], bl["block_k"]
    if info.get("packed"):
        bk = max((min(bk, k) // 8) * 8, 8)  # ops._bnn_packed_impl byte rule
    sub = bl.get("block_k_sub")
    align = {"align": PACKED_WINDOW} if info.get("packed") else {}
    bks = autotune.pick_block_k_sub(bm, bn, bk, requested=sub, **align)
    return dict(block_m=bm, block_n=bn, block_k=bk, block_k_sub=bks)


def matmul_vmem_bytes(route: str, bl: Dict[str, int]) -> int:
    """Resident VMEM working set of one grid step: operand windows + output
    window(s) + the (bm, bk_sub, bn) broadcast sub-tile the beat
    materializes in VREGs/VMEM. Quantized operand windows count at their
    storage width; the sub-tile always widens to f32."""
    bm, bn, bk = bl["block_m"], bl["block_n"], bl["block_k"]
    bks = bl["block_k_sub"]
    sub = bm * bks * bn * 4
    if route == "cac_hw":
        return bm * bk * 4 + 2 * bk * bn * 4 + bm * bn * 4 + sub
    if route == "cac_train":
        # fwd: (x, w, beta) in, y out. bwd (fused, worst case): 4 operand
        # windows + 3 output windows, all f32, same beat sub-tile.
        fwd = bm * bk * 4 + 2 * bk * bn * 4 + bm * bn * 4 + sub
        bwd = (bm * bk + 2 * bk * bn + bm * bn) * 4 \
            + (bm * bk + 2 * bk * bn) * 4 + sub
        return max(fwd, bwd)
    if route in ("bnn", "bnn_train"):
        fwd = bm * bk * 4 + bk * bn * 4 + bm * bn * 4 + sub
        if route == "bnn_train":
            # bwd dx call: (x, w, g) windows + dx out; dw call symmetric
            bwd = (bm * bk + bk * bn + bm * bn) * 4 + max(bm * bk, bk * bn) * 4
            return max(fwd, bwd)
        return fwd
    if route == "bnn_packed":
        return bm * bk * 4 + (bk // 8) * bn + bm * bn * 4 + sub
    if route == "qnn8":
        return bm * bk + bk * bn + bn * 4 + bm * bn * 4 + sub
    raise ValueError(f"no VMEM model for matmul route {route!r}")


def paged_attn_vmem_bytes(c: int, bs: int, bh: int, g: int, d: int,
                          *, quantized: bool = False) -> int:
    """One grid step of the fused paged-attention kernel: q/out windows
    (1, bh, g*C, D), k/v pool windows (1, bs, bh, D), per-block scales when
    quantized, and the online-softmax scratch (m, l, acc)."""
    kv_w = 1 if quantized else 4
    q_out = 2 * c * bh * g * d * 4
    kv = 2 * bs * bh * d * kv_w + (2 * bs * bh * 4 if quantized else 0)
    scratch = c * bh * g * (2 + d) * 4  # m, l, acc
    return q_out + kv + scratch


# ---------------------------------------------------------------------------
# Contract checks (pure arithmetic — also the seeded-violation entry points)
# ---------------------------------------------------------------------------


def _f(code: str, where: str, message: str, hint: str, **extra) -> Finding:
    return Finding(pass_name=PASS, code=code, where=where, message=message,
                   hint=hint, extra=extra)


def check_matmul_contract(route: str, m: int, k: int, n: int,
                          blocks: Optional[Dict[str, int]] = None,
                          where: Optional[str] = None,
                          vmem_budget: int = KERNEL_VMEM_BUDGET,
                          ) -> Tuple[List[Finding], Dict]:
    """Divisibility / padding / index-map / VMEM checks for one matmul-route
    problem. ``blocks`` overrides the autotune resolution (how tests seed
    violations). Returns (findings, entry) where entry is the JSON row."""
    where = where or f"{route}[{m}x{k}x{n}]"
    info = ROUTE_INFO[route]
    bl = _resolve(route, m, k, n, blocks)
    bm, bn, bk, bks = (bl["block_m"], bl["block_n"], bl["block_k"],
                       bl["block_k_sub"])
    findings: List[Finding] = []
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    grid = (mp // bm, np_ // bn, kp // bk)
    # KCV002: padding coverage + sub-tile/byte legality
    for dim, (p, b) in dict(m=(mp, bm), n=(np_, bn), k=(kp, bk)).items():
        if b < 1 or p % b:
            findings.append(_f(
                "KCV002", where,
                f"padded {dim}={p} not divisible by block_{dim}={b}",
                "pad to a block multiple (ops._round_up) or shrink the block",
                dim=dim, padded=p, block=b))
    if bks < 1 or bk % bks:
        findings.append(_f(
            "KCV002", where,
            f"block_k_sub={bks} does not divide block_k={bk}",
            "pick_block_k_sub must return a divisor of block_k",
            block_k=bk, block_k_sub=bks))
    if info.get("packed"):
        if k % 8:
            findings.append(_f(
                "KCV002", where,
                f"packed-bitplane route needs K % 8 == 0, got K={k}",
                "pad K to a byte multiple before packing (core.backend."
                "pack_signs asserts this)", k=k))
        if bk % 8 or bks % 8:
            findings.append(_f(
                "KCV002", where,
                f"packed K beats must slice whole bytes: block_k={bk}, "
                f"block_k_sub={bks}",
                "use pick_block_k_sub (sub-tiles are multiples of 8)",
                block_k=bk, block_k_sub=bks))
    # KCV003: last-step index-map bounds per axis (block-index maps i -> i*b)
    for dim, (p, b, gdim) in dict(
            m=(mp, bm, grid[0]), n=(np_, bn, grid[1]),
            k=(kp, bk, grid[2])).items():
        last_start = (gdim - 1) * b
        if gdim < 1 or last_start + b > p or last_start < 0:
            findings.append(_f(
                "KCV003", where,
                f"index map exceeds padded operand on {dim}: last block "
                f"[{last_start}, {last_start + b}) vs padded {p}",
                "grid must be ceil(padded/block) with block-index maps",
                dim=dim, grid=gdim, block=b, padded=p))
    # KCV004: VMEM working set vs the shared budget
    vmem = matmul_vmem_bytes(route, bl)
    if vmem > vmem_budget:
        findings.append(_f(
            "KCV004", where,
            f"block working set {vmem} B exceeds VMEM budget {vmem_budget} B",
            "shrink block_k_sub / block_n (autotune.SUBTILE_BUDGET) or the "
            "K depth for this path", vmem_bytes=vmem, budget=vmem_budget))
    entry = dict(route=route, m=m, k=k, n=n, blocks=dict(bl), grid=list(grid),
                 vmem_bytes=int(vmem), vmem_budget=int(vmem_budget),
                 ok=not findings)
    return findings, entry


def check_paged_attn_contract(n_slots: int, max_len: int, block_size: int,
                              hq: int, hkv: int, d: int, c: int = 1,
                              blocks: Optional[Dict[str, int]] = None,
                              where: Optional[str] = None,
                              quantized: bool = False,
                              vmem_budget: int = KERNEL_VMEM_BUDGET,
                              ) -> Tuple[List[Finding], Dict]:
    """Contract checks for the fused paged-attention route."""
    where = where or f"paged_attn[{n_slots}x{max_len}x{block_size}x{d}x{hkv}]"
    bl = autotune.get_paged_blocks(n_slots, max_len, block_size, d, hkv,
                                   overrides=blocks or None)
    bh = bl["block_h"]
    findings: List[Finding] = []
    if bh < 1 or hkv % bh:
        findings.append(_f(
            "KCV002", where,
            f"block_h={bh} does not divide kv_heads={hkv}",
            "get_paged_blocks clamps to a divisor; explicit overrides must too",
            block_h=bh, kv_heads=hkv))
    if max_len % block_size:
        findings.append(_f(
            "KCV002", where,
            f"max_len={max_len} not a multiple of block_size={block_size}",
            "the block table assumes max_len // block_size whole blocks",
            max_len=max_len, block_size=block_size))
    if hq % hkv:
        findings.append(_f(
            "KCV002", where,
            f"GQA group: n_heads={hq} not a multiple of kv_heads={hkv}",
            "the (C, bh, g, d) reshape needs an integer group size",
            n_heads=hq, kv_heads=hkv))
    g = hq // max(hkv, 1) if hkv and hq % hkv == 0 else 1
    t = max(max_len // max(block_size, 1), 1)
    grid = (n_slots, max(hkv // max(bh, 1), 1), t)
    vmem = paged_attn_vmem_bytes(c, block_size, bh, g, d, quantized=quantized)
    if vmem > vmem_budget:
        findings.append(_f(
            "KCV004", where,
            f"paged-attn step working set {vmem} B exceeds VMEM budget "
            f"{vmem_budget} B",
            "shrink block_h (heuristic_paged_blocks already budgets; check "
            "explicit overrides)", vmem_bytes=vmem, budget=vmem_budget))
    entry = dict(route="paged_attn", n_slots=n_slots, max_len=max_len,
                 block_size=block_size, hq=hq, hkv=hkv, d=d, c=c,
                 blocks=dict(bl), grid=list(grid), vmem_bytes=int(vmem),
                 vmem_budget=int(vmem_budget), ok=not findings)
    return findings, entry


# ---------------------------------------------------------------------------
# Abstract evaluation (KCV005) — runs the REAL wrapper under jax.eval_shape
# ---------------------------------------------------------------------------


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def abstract_eval_route(route: str, m: int, k: int, n: int,
                        cfg=None) -> Tuple[Optional[str], Tuple]:
    """eval_shape the route wrapper on its dtype signature; returns
    (error or None, out_shape). No kernel executes — BlockSpecs, grids and
    index maps are constructed and validated by Pallas tracing."""
    fn = ops.KERNEL_ROUTES[route]
    try:
        if route == "cac_hw":
            out = jax.eval_shape(fn, _sds((m, k), _F32), _sds((k, n), _F32),
                                 _sds((k, n), _F32))
        elif route == "cac_train":
            out = jax.eval_shape(fn, _sds((m, k), _F32), _sds((k, n), _F32),
                                 _sds((k, n), _F32))
        elif route in ("bnn", "bnn_train"):
            out = jax.eval_shape(fn, _sds((m, k), _F32), _sds((k, n), _F32))
        elif route == "bnn_packed":
            if k % 8:
                return None, ()  # byte-pack violation reported by KCV002
            out = jax.eval_shape(fn, _sds((m, k), _F32),
                                 _sds((k // 8, n), jnp.uint8))
        elif route == "qnn8":
            out = jax.eval_shape(
                functools.partial(fn, x_scale=0.05),
                _sds((m, k), jnp.int8), _sds((k, n), jnp.int8),
                _sds((1, n), _F32))
        elif route == "paged_attn":
            bs, max_len = 16, 256
            t = max_len // bs
            hkv, hq, d = cfg.n_kv_heads, cfg.n_heads, cfg.hd
            if hq % hkv:
                return None, ()  # GQA contract violation reported by KCV002
            out = jax.eval_shape(
                fn,
                _sds((m, 1, hq, d), _F32),
                _sds((m * t + 1, bs, hkv, d), _F32),
                _sds((m * t + 1, bs, hkv, d), _F32),
                _sds((m, t), jnp.int32),
                _sds((m, 1), jnp.int32),
            )
        else:
            return f"no abstract-eval signature for route {route!r}", ()
    except Exception as e:  # tracing failure IS the finding
        return f"{type(e).__name__}: {e}", ()
    expected = (m, 1, cfg.n_heads, cfg.hd) if route == "paged_attn" else (m, n)
    if tuple(out.shape) != expected:
        return f"output shape {tuple(out.shape)} != expected {expected}", out.shape
    if out.dtype != _F32:
        return f"output dtype {out.dtype} != float32 (silent upcast/downcast)", ()
    return None, tuple(out.shape)


# ---------------------------------------------------------------------------
# Autotune-key and registry consistency (KCV006 / KCV007)
# ---------------------------------------------------------------------------


def _key_findings(path: str, m: int, k: int, n: int, where: str) -> List[Finding]:
    key = autotune.cache_key(path, m, k, n)
    parsed = autotune.parse_cache_key(key)
    if parsed is None or parsed["path"] != path or parsed["shape"] != (m, k, n):
        return [_f("KCV006", where,
                   f"cache key {key!r} does not round-trip to "
                   f"({path!r}, {(m, k, n)})",
                   "autotune.cache_key and parse_cache_key must stay inverse",
                   key=key)]
    return []


def _registry_findings() -> List[Finding]:
    from repro.core.backend import LinearSpec, registered_backends

    findings: List[Finding] = []
    known_paths = set(autotune._BASE) | {autotune.PAGED_ATTN_PATH}
    for name, backend in registered_backends().items():
        spec = LinearSpec(mode=name, impl="pallas", pack_signs=True)
        for phase in ("train", "serve"):
            route = backend.kernel_route(spec, phase)
            path = backend.autotune_path(spec, phase)
            where = f"backend:{name}/{phase}"
            if route is not None and route not in ops.KERNEL_ROUTES:
                findings.append(_f(
                    "KCV006", where,
                    f"kernel_route {route!r} not in KERNEL_ROUTES",
                    "register the route in kernels/ops.py or fix the backend"))
            if path is not None and path not in known_paths:
                findings.append(_f(
                    "KCV006", where,
                    f"autotune_path {path!r} unknown to kernels/autotune.py",
                    "add a _BASE entry for the path or fix the backend"))
            if (route is None) != (path is None):
                findings.append(_f(
                    "KCV006", where,
                    f"kernel_route={route!r} but autotune_path={path!r} — a "
                    "routed kernel must resolve blocks somewhere",
                    "define both (or neither) for each phase"))
            key = backend.autotune_key(spec, phase, 64, 128, 256)
            if path is not None and key != autotune.cache_key(path, 64, 128, 256):
                findings.append(_f(
                    "KCV006", where,
                    f"autotune_key {key!r} disagrees with cache_key({path!r})",
                    "QuantBackend.autotune_key must delegate to autotune."
                    "cache_key"))
    return findings


def _cache_findings() -> List[Finding]:
    return [
        _f("KCV007", f"autotune-cache:{key}",
           f"invalid on-disk autotune cache entry: {reason}",
           "delete the entry (or the cache file at autotune.cache_path()); "
           "it was ignored at load, but something wrote it",
           key=key, reason=reason)
        for key, reason in autotune.invalid_cache_entries()
    ]


# ---------------------------------------------------------------------------
# The pass
# ---------------------------------------------------------------------------


def run(archs=None, eval_shapes: bool = True,
        vmem_budget: int = KERNEL_VMEM_BUDGET) -> Report:
    rep = Report(passes_run=[PASS])
    archs = list(archs) if archs is not None else list(ARCH_NAMES)

    # KCV001: metadata <-> route table coverage
    missing = sorted(set(ops.KERNEL_ROUTES) - set(ROUTE_INFO))
    stale = sorted(set(ROUTE_INFO) - set(ops.KERNEL_ROUTES))
    for r in missing:
        rep.add(_f("KCV001", f"route:{r}",
                   "KERNEL_ROUTES entry has no contract metadata",
                   "add a ROUTE_INFO entry (kind/path/dtypes) so the "
                   "verifier covers the new route"))
    for r in stale:
        rep.add(_f("KCV001", f"route:{r}",
                   "contract metadata names a route that no longer exists",
                   "drop the stale ROUTE_INFO entry"))

    entries: List[Dict] = []
    matmul_routes = [r for r, i in ROUTE_INFO.items()
                     if i["kind"] == "matmul" and r in ops.KERNEL_ROUTES]
    for arch in archs:
        cfg = get_config(arch)
        gemms = config_gemms(cfg)
        for route in matmul_routes:
            worst = None
            for gemm_name, (k, n) in gemms.items():
                for m in (M_DECODE, M_PREFILL):
                    where = f"{route}/{arch}/{gemm_name}[{m}x{k}x{n}]"
                    fs, entry = check_matmul_contract(
                        route, m, k, n, where=where, vmem_budget=vmem_budget)
                    rep.findings.extend(fs)
                    entry.update(arch=arch, gemm=gemm_name)
                    if worst is None or entry["vmem_bytes"] > worst["vmem_bytes"]:
                        worst = entry
                    _keyfs = _key_findings(ROUTE_INFO[route]["path"], m, k, n,
                                           where)
                    rep.findings.extend(_keyfs)
            if eval_shapes and worst is not None:
                err, _shape = abstract_eval_route(
                    route, worst["m"], worst["k"], worst["n"], cfg=cfg)
                if err:
                    rep.add(_f("KCV005", f"{route}/{arch}",
                               f"abstract eval failed: {err}",
                               "the wrapper's shape/dtype contract broke — "
                               "run the route's parity tests",
                               m=worst["m"], k=worst["k"], n=worst["n"]))
                    worst["eval_shape_ok"] = False
                else:
                    worst["eval_shape_ok"] = True
            entries.append(worst)
        if "paged_attn" in ops.KERNEL_ROUTES:
            for c, label in ((1, "decode"), (32, "chunk")):
                fs, entry = check_paged_attn_contract(
                    M_DECODE, 256, 16, cfg.n_heads, cfg.n_kv_heads, cfg.hd,
                    c=c, where=f"paged_attn/{arch}/{label}",
                    vmem_budget=vmem_budget)
                rep.findings.extend(fs)
                entry.update(arch=arch, gemm=label)
                if c == 1 and eval_shapes:
                    err, _shape = abstract_eval_route(
                        "paged_attn", M_DECODE, 0, 0, cfg=cfg)
                    if err:
                        rep.add(_f("KCV005", f"paged_attn/{arch}",
                                   f"abstract eval failed: {err}",
                                   "fused paged-attention wrapper "
                                   "contract broke"))
                        entry["eval_shape_ok"] = False
                    else:
                        entry["eval_shape_ok"] = True
                entries.append(entry)

    rep.findings.extend(_registry_findings())
    rep.findings.extend(_cache_findings())

    covered = {(e["route"], e["arch"]) for e in entries if e}
    expected = {(r, a) for r in ops.KERNEL_ROUTES for a in archs}
    for route, arch in sorted(expected - covered):
        rep.add(_f("KCV001", f"{route}/{arch}",
                   "route x config pair produced no contract entry",
                   "the verifier must cover 100% of KERNEL_ROUTES x configs"))

    rep.data[PASS] = {
        "n_routes": len(ops.KERNEL_ROUTES),
        "n_archs": len(archs),
        "coverage": f"{len(covered)}/{len(expected)}",
        "vmem_budget": int(vmem_budget),
        "entries": entries,
        "invalid_cache_entries": [
            {"key": k, "reason": r} for k, r in autotune.invalid_cache_entries()
        ],
    }
    return rep
