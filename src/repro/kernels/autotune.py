"""Shape-adaptive block selection for the Pallas kernel wrappers (DESIGN.md §2).

Two layers, both deterministic by default:

  1. ``heuristic_blocks(m, k, n, path)`` — a small closed-form table keyed on
     the contraction *path* (hw_fwd / train_fwd / train_bwd for the CAC
     stack; bnn / bnn_bwd / qnn8 for the baseline backends) and adapted to
     the problem shape: skinny-M (decode-like) problems widen the N block to
     keep the VPU lanes full, long-K problems lengthen the K block to
     amortize output-block traffic, backward paths shrink block_k because
     multiple live output accumulators raise VMEM pressure, and the int8
     MXU path (qnn8) deepens K further because its operand blocks are 4x
     smaller than f32 at equal tile counts.
  2. ``measured_blocks(...)`` — an optional measured search that times the
     real kernel call over a candidate list and keeps the winner in memory,
     and in the JSON file named by ``REPRO_AUTOTUNE_CACHE`` when that is
     set, keyed on ``backend:path:MxKxN``. With the variable unset no file
     feeds the tiles: a run compiles from the heuristic table alone.

``get_blocks`` merges heuristic < cached < explicit caller overrides and then
clamps to legal tile sizes for the (padded) problem, so every kernel wrapper
funnels through one resolution point.  ``pick_block_k_sub`` chooses the
sub-tile depth of the vectorized beat loop (kernels/cac_matmul.py): the
largest sublane-aligned divisor of the block's 128-lane read window
(kernels/beats.py) whose (bm, bk_sub, bn) broadcast-compare stays inside the
VREG working-set budget.
"""
from __future__ import annotations

import json
import os
import re
import threading
from typing import Dict, List, Optional, Tuple

import jax

from .beats import LANE, beat_window
from .paged_attn import legal_block_h, pool_row_lanes

__all__ = [
    "DEFAULT_BLOCKS",
    "heuristic_blocks",
    "get_blocks",
    "measured_blocks",
    "pick_block_k_sub",
    "cache_key",
    "cache_path",
    "clear_cache",
    "parse_cache_key",
    "validate_cache_entry",
    "invalid_cache_entries",
    "paged_attn_cache_key",
    "heuristic_paged_blocks",
    "get_paged_blocks",
    "measured_paged_blocks",
]

DEFAULT_BLOCKS = dict(block_m=256, block_n=256, block_k=512)

# f32 elements of one (bm, bk_sub, bn) sub-tile broadcast; 2^19 el = 2 MiB,
# a conservative VREG-spill working set (the VPU streams it in (8,128) regs).
SUBTILE_BUDGET = 1 << 19

# Per-path base blocks. Paths:
#   hw_fwd    — serving comparator contraction (x, tau, s)
#   train_fwd — Sign(x*w + beta) forward
#   train_bwd — STE backward (fused or two-call; 3 output accumulators)
#   bnn       — sign(x) @ sign(w) MXU forward (train fwd + serve, incl. the
#               packed-bitplane serve kernel)
#   bnn_bwd   — BNN SignSTE backward (two masked MXU contractions)
#   qnn8      — int8 x int8 -> int32 serve matmul + fused dequant
#   qnn       — legacy alias of qnn8 (pre-registry cache entries)
_BASE: Dict[str, Dict[str, int]] = {
    "hw_fwd": dict(block_m=256, block_n=256, block_k=512),
    "train_fwd": dict(block_m=256, block_n=256, block_k=512),
    "train_bwd": dict(block_m=256, block_n=256, block_k=256),
    "bnn": dict(block_m=256, block_n=256, block_k=512),
    "bnn_bwd": dict(block_m=256, block_n=256, block_k=256),
    "qnn8": dict(block_m=256, block_n=256, block_k=512),
    "qnn": dict(block_m=256, block_n=256, block_k=512),
}

# MXU baseline paths whose VMEM operand blocks are int8: the same VMEM
# budget holds 4x the K depth of an f32 block. The bnn paths do NOT qualify:
# their x/w blocks arrive as f32 (signs are computed in-kernel), so they
# keep the f32 K-depth rules.
_INT_PATHS = ("qnn8", "qnn")

# current path -> pre-registry alias it may still be cached under on disk.
# Measured-cache lookups consult the alias when the canonical key misses,
# so entries tuned before the registry rename keep being honored.
_LEGACY_PATH_ALIASES = {"qnn8": "qnn"}

_SUBLANE, _LANE = 8, LANE  # f32 min tile (sublane x lane)


def _round_up(v: int, b: int) -> int:
    return -(-v // b) * b


def heuristic_blocks(m: int, k: int, n: int, path: str = "train_fwd") -> Dict[str, int]:
    """Deterministic shape-adaptive block table. Returns unclamped targets;
    ``get_blocks`` applies the legality clamp."""
    base = dict(_BASE.get(path, DEFAULT_BLOCKS))
    bm, bn, bk = base["block_m"], base["block_n"], base["block_k"]
    if m <= 64:
        # decode-like: few rows, so spend the VMEM on wider N instead
        bm, bn = 64, min(2 * bn, 512)
    if k >= 4096 and path not in ("train_bwd", "bnn_bwd"):
        # long contractions: longer K blocks cut output-block init/flush count
        bk = 1024
    if n <= 128:
        # narrow outputs: reclaim the N budget into K depth
        bk = max(bk, 1024) if path not in ("train_bwd", "bnn_bwd") else bk
    if path in _INT_PATHS and k >= 2048:
        # int8/packed operands: double K depth at the same VMEM footprint
        bk = max(bk, 2048 if k >= 8192 else 1024)
    return dict(block_m=bm, block_n=bn, block_k=bk)


def _clamp(m: int, k: int, n: int, bl: Dict[str, int]) -> Dict[str, int]:
    out = dict(bl)
    out["block_m"] = max(min(bl["block_m"], _round_up(m, _SUBLANE)), 1)
    out["block_n"] = max(min(bl["block_n"], _round_up(n, _LANE)), 1)
    out["block_k"] = max(min(bl["block_k"], k), 1)
    return out


def pick_block_k_sub(bm: int, bn: int, bk: int, requested: Optional[int] = None,
                     budget: int = SUBTILE_BUDGET, align: int = LANE) -> int:
    """Beat width of a block with K extent ``bk``: a divisor of the block's
    read window (``beats.beat_window(bk, align)``: ``align`` when it divides
    bk, else bk itself) that is a multiple of 8 (the f32 sublane tile, so
    every beat's operand rows start on a tile row, and a packed beat slices
    whole bytes). Returns the largest such width with ``bm * bk_sub * bn <=
    budget`` (or ``<= requested`` when given), else the smallest legal
    width. A window with no such divisor is a single beat."""
    window = beat_window(bk, align)
    legal = [d for d in range(_SUBLANE, window + 1, _SUBLANE)
             if window % d == 0] or [window]
    cap = requested if requested else max(budget // max(bm * bn, 1), 1)
    fits = [d for d in legal if d <= cap]
    return max(fits) if fits else min(legal)


# ---------------------------------------------------------------------------
# Measured-search mode with on-disk cache
# ---------------------------------------------------------------------------

_cache_lock = threading.Lock()
_cache: Optional[Dict[str, Dict[str, int]]] = None


def cache_path() -> Optional[str]:
    """The measured-block cache file named by ``REPRO_AUTOTUNE_CACHE``, or
    None: then no file feeds the tiles, which come from the heuristic table
    (and from searches measured in this process)."""
    return os.environ.get("REPRO_AUTOTUNE_CACHE") or None


def cache_key(path: str, m: int, k: int, n: int) -> str:
    """Public cache-key form: ``backend:path:MxKxN`` — what backends report
    via ``QuantBackend.autotune_key`` and what the JSON cache is keyed on."""
    return f"{jax.default_backend()}:{path}:{m}x{k}x{n}"


_cache_key = cache_key  # internal alias (pre-registry name)


# ---------------------------------------------------------------------------
# Cache-entry validation (the stale-cache bugfix)
# ---------------------------------------------------------------------------
# A hand-edited / corrupted / version-skewed autotune.json used to flow its
# blocks straight into the kernel wrappers: junk values survived the legality
# clamp only by accident (non-int types crashed inside pallas_call; a
# matmul-shaped entry under a paged key silently mistuned the kernel). Every
# entry is now validated on load against the shape its OWN key encodes —
# invalid entries are dropped (and remembered, so the kernel-contract
# verifier can surface them as findings) instead of silently routing a
# kernel with wrong blocks.

_MATMUL_KEY_RE = re.compile(r"^([A-Za-z0-9_]+):([A-Za-z0-9_]+):(\d+)x(\d+)x(\d+)$")
_PAGED_KEY_RE = re.compile(
    r"^([A-Za-z0-9_]+):paged_attn:(\d+)x(\d+)x(\d+)x(\d+)x(\d+)$")

_MATMUL_BLOCK_FIELDS = {"block_m", "block_n", "block_k", "block_k_sub"}
_PAGED_BLOCK_FIELDS = {"block_h"}

# invalid entries seen by the last _load_cache, as (key, reason) pairs
_invalid: List[Tuple[str, str]] = []


def parse_cache_key(key: str) -> Optional[Dict[str, object]]:
    """Decompose an on-disk cache key.

    Returns ``{"backend", "path", "shape": (m, k, n)}`` for matmul keys,
    ``{"backend", "path": "paged_attn", "shape": (slots, len, bs, hd, kv)}``
    for paged-attention keys, and None for unparseable keys."""
    m = _PAGED_KEY_RE.match(key)
    if m:
        return {"backend": m.group(1), "path": "paged_attn",
                "shape": tuple(int(g) for g in m.groups()[1:])}
    m = _MATMUL_KEY_RE.match(key)
    if m:
        return {"backend": m.group(1), "path": m.group(2),
                "shape": (int(m.group(3)), int(m.group(4)), int(m.group(5)))}
    return None


def validate_cache_entry(key: str, blocks) -> Optional[str]:
    """None when (key, blocks) is a well-formed, legal cache entry; else a
    human-readable reason. Legality is checked against the shape tuple the
    key itself encodes, so an entry can never apply blocks tuned (or
    corrupted) for a different problem."""
    parsed = parse_cache_key(key)
    if parsed is None:
        return "unparseable key (expected backend:path:MxKxN or paged form)"
    if not isinstance(blocks, dict) or not blocks:
        return "entry is not a non-empty block dict"
    fields = (_PAGED_BLOCK_FIELDS if parsed["path"] == "paged_attn"
              else _MATMUL_BLOCK_FIELDS)
    unknown = set(blocks) - fields
    if unknown:
        return f"unknown block field(s) for path {parsed['path']!r}: {sorted(unknown)}"
    for f, v in blocks.items():
        if isinstance(v, bool) or not isinstance(v, int) or v < 1:
            return f"{f}={v!r} is not a positive int"
    shape = parsed["shape"]
    if parsed["path"] == "paged_attn":
        kv_heads = shape[4]
        bh = blocks.get("block_h", 1)
        if bh > kv_heads or kv_heads % bh:
            return f"block_h={bh} does not divide kv_heads={kv_heads}"
        return None
    m_, k_, n_ = shape
    bl = {**DEFAULT_BLOCKS, **{f: v for f, v in blocks.items() if f != "block_k_sub"}}
    clamped = _clamp(m_, k_, n_, bl)
    drift = {f: (bl[f], clamped[f]) for f in ("block_m", "block_n", "block_k")
             if f in blocks and clamped[f] != blocks[f]}
    if drift:
        return f"blocks illegal for shape {m_}x{k_}x{n_}: {drift}"
    sub = blocks.get("block_k_sub")
    if sub is not None and bl["block_k"] % sub:
        return f"block_k_sub={sub} does not divide block_k={bl['block_k']}"
    return None


def invalid_cache_entries() -> List[Tuple[str, str]]:
    """(key, reason) for every on-disk entry the last load rejected — the
    kernel-contract verifier reports these as findings."""
    _load_cache()
    with _cache_lock:
        return list(_invalid)


def _load_cache() -> Dict[str, Dict[str, int]]:
    global _cache
    with _cache_lock:
        if _cache is None:
            _invalid.clear()
            path = cache_path()
            raw = {}
            if path is not None:
                try:
                    with open(path) as fh:
                        raw = json.load(fh)
                except (OSError, ValueError):
                    raw = {}
            if not isinstance(raw, dict):
                raw = {}
            _cache = {}
            for k_, v in raw.items():
                reason = validate_cache_entry(k_, v)
                if reason is None:
                    _cache[k_] = dict(v)
                else:
                    _invalid.append((k_, reason))
        return _cache


def _store_cache(key: str, blocks: Dict[str, int]) -> None:
    global _cache
    _load_cache()  # merge into whatever is already on disk
    with _cache_lock:
        cur = dict(_cache or {})
        cur[key] = {k_: int(v) for k_, v in blocks.items()}
        _cache = cur
        f = cache_path()
        if f is None:
            return  # no cache file named: the winner lives in memory only
        try:
            os.makedirs(os.path.dirname(f), exist_ok=True)
            tmp = f + ".tmp"
            with open(tmp, "w") as fh:
                json.dump(cur, fh, indent=1, sort_keys=True)
            os.replace(tmp, f)
        except OSError:
            pass  # cache is best-effort; heuristics still apply


def clear_cache() -> None:
    """Drop the in-memory cache (tests; does not delete the file)."""
    global _cache
    with _cache_lock:
        _cache = None


def get_blocks(
    m: int,
    k: int,
    n: int,
    path: str = "train_fwd",
    overrides: Optional[Dict[str, int]] = None,
    use_cache: bool = True,
) -> Dict[str, int]:
    """Resolve (block_m, block_n, block_k[, block_k_sub]) for one call site.

    Priority: explicit overrides > measured cache > heuristic table; the
    result is clamped to legal tile sizes for the padded problem."""
    bl = heuristic_blocks(m, k, n, path)
    if use_cache:
        cached = _load_cache()
        hit = cached.get(_cache_key(path, m, k, n))
        if hit is None and path in _LEGACY_PATH_ALIASES:
            hit = cached.get(_cache_key(_LEGACY_PATH_ALIASES[path], m, k, n))
        if hit:
            bl.update(hit)
    sub = None
    if overrides:
        ov = {kk: int(v) for kk, v in overrides.items() if v is not None}
        sub = ov.pop("block_k_sub", None)
        unknown = set(ov) - {"block_m", "block_n", "block_k"}
        if unknown:
            raise TypeError(f"unknown block override(s): {sorted(unknown)}")
        bl.update(ov)
    out = _clamp(m, k, n, bl)
    if sub is not None:
        out["block_k_sub"] = sub
    return out


_CANDIDATES = [
    dict(block_m=128, block_n=128, block_k=256),
    dict(block_m=128, block_n=256, block_k=512),
    dict(block_m=256, block_n=256, block_k=256),
    dict(block_m=256, block_n=256, block_k=512),
    dict(block_m=256, block_n=512, block_k=512),
    dict(block_m=512, block_n=256, block_k=1024),
]


def measured_blocks(
    path: str,
    m: int,
    k: int,
    n: int,
    *,
    candidates=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: Optional[bool] = None,
    seed: int = 0,
) -> Dict[str, int]:
    """Time the real kernel over a candidate list; persist + return the best.

    The measured winner goes into the cache (in memory, and the file
    ``REPRO_AUTOTUNE_CACHE`` names when set) so later ``get_blocks`` calls
    for the same (backend, path, shape) pick it up without re-timing."""
    import time

    import jax.numpy as jnp

    from . import ops  # deferred: ops imports this module

    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (m, k), jnp.float32)
    w = jax.random.normal(ks[1], (k, n), jnp.float32) * 0.1
    b = jax.random.normal(ks[2], (k, n), jnp.float32) * 0.1
    g = jax.random.normal(ks[3], (m, n), jnp.float32)

    def runner(bl):
        if path == "hw_fwd":
            return lambda: ops.cac_matmul(x, w, b, interpret=interpret, **bl)
        if path == "train_fwd":
            return lambda: ops.cac_train_matmul(x, w, b, interpret=interpret, **bl)
        if path == "train_bwd":
            f = lambda: jax.vjp(
                lambda *a: ops.cac_train_matmul(*a, interpret=interpret, **bl), x, w, b
            )[1](g)
            return f
        if path == "bnn":
            return lambda: ops.bnn_matmul(x, w, interpret=interpret, **bl)
        if path == "bnn_bwd":
            return lambda: jax.vjp(
                lambda *a: ops.bnn_train_matmul(*a, interpret=interpret, **bl), x, w
            )[1](g)
        if path in ("qnn8", "qnn"):
            xi = jnp.clip(jnp.round(x * 16.0), -127, 127).astype(jnp.int8)
            wi = jnp.clip(jnp.round(w * 64.0), -127, 127).astype(jnp.int8)
            ws = jnp.abs(w).max(axis=0, keepdims=True) / 127.0
            return lambda: ops.qnn_matmul(xi, wi, ws, 0.05, interpret=interpret, **bl)
        raise ValueError(f"no measured runner for path {path!r}")

    best, best_t = None, float("inf")
    seen = set()
    timings = []  # (blocks, mean_s) per legal candidate, for the trace event
    for cand in candidates or _CANDIDATES:
        cl = _clamp(m, k, n, {**DEFAULT_BLOCKS, **cand})
        key = tuple(sorted(cl.items()))
        if key in seen:  # distinct candidates can clamp to the same legal tile
            continue
        seen.add(key)
        fn = runner(cl)
        try:
            for _ in range(warmup):
                jax.block_until_ready(fn())
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(fn())
            t = (time.perf_counter() - t0) / iters
        except Exception:
            continue  # illegal tiling for this backend: skip candidate
        timings.append((cl, t))
        if t < best_t:
            best, best_t = cl, t
    if best is None:
        best = _clamp(m, k, n, heuristic_blocks(m, k, n, path))
    _store_cache(_cache_key(path, m, k, n), best)
    _trace_search(f"{path}:{m}x{k}x{n}", best, best_t, timings)
    return best


def _trace_search(shape_key: str, winner: Dict[str, int], best_t: float,
                  timings) -> None:
    """Report one measured search to the process-global tracer (installed by
    ``launch.serve --trace-out`` via ``obs.trace.set_tracer``) as an
    ``autotune`` event on the ``autotune`` track, with per-candidate means."""
    from repro.obs.trace import get_tracer

    tracer = get_tracer()
    if not tracer.enabled:
        return
    tracer.event(
        "autotune", track="autotune", shape=shape_key, winner=dict(winner),
        best_ms=(best_t * 1e3 if best_t != float("inf") else None),
        n_candidates=len(timings),
        candidates=[{"blocks": dict(bl), "ms": t * 1e3} for bl, t in timings],
    )


# ---------------------------------------------------------------------------
# "paged_attn" path: the fused paged-attention kernel (kernels/paged_attn.py)
# ---------------------------------------------------------------------------
#
# Not an (M, K, N) contraction — the problem is keyed on the serving shape
# ``(n_slots, max_len, block_size, hd)`` (+ kv_heads, which the only tunable
# must divide) and the block table has one knob: ``block_h``, the kv heads
# folded into one grid step. The pool is lane-dense, so ``block_h * hd``
# must be a multiple of 128 lanes or the whole row (``legal_block_h``).
# Each step's VMEM working set is
# ``2 * block_size * block_h * hd`` pool elements (k + v) plus the per-step
# q/accumulator tiles, so block_h trades grid-step count against VMEM
# pressure exactly like block_k_sub does for the matmul beats. The same
# on-disk JSON cache stores measured winners under ``paged_attn_cache_key``.

PAGED_ATTN_PATH = "paged_attn"


def paged_attn_cache_key(n_slots: int, max_len: int, block_size: int,
                         hd: int, kv_heads: int) -> str:
    """Cache-key form of the paged-attention problem shape:
    ``backend:paged_attn:SxLxBxDxH``."""
    return (f"{jax.default_backend()}:{PAGED_ATTN_PATH}:"
            f"{n_slots}x{max_len}x{block_size}x{hd}x{kv_heads}")


def heuristic_paged_blocks(n_slots: int, max_len: int, block_size: int,
                           hd: int, kv_heads: int) -> Dict[str, int]:
    """Largest legal ``block_h`` (``legal_block_h``) whose (k + v) step tile
    stays inside the sub-tile budget. Serving shapes are small enough that
    this is usually ``kv_heads`` itself (one grid step per (row, block))."""
    bh = max(kv_heads, 1)
    while bh > 1 and 2 * block_size * bh * hd > SUBTILE_BUDGET:
        bh -= 1
    return {"block_h": legal_block_h(kv_heads, hd, bh)}


def _clamp_paged(kv_heads: int, hd: int, bl: Dict[str, int]) -> Dict[str, int]:
    return {"block_h": legal_block_h(max(kv_heads, 1), hd,
                                     int(bl.get("block_h", kv_heads)))}


def get_paged_blocks(
    n_slots: int,
    max_len: int,
    block_size: int,
    hd: int,
    kv_heads: int,
    overrides: Optional[Dict[str, int]] = None,
    use_cache: bool = True,
) -> Dict[str, int]:
    """Resolve ``{"block_h"}`` for one paged-attention call site. Same
    priority order as ``get_blocks``: explicit overrides > measured cache >
    heuristic, clamped by ``legal_block_h``."""
    bl = heuristic_paged_blocks(n_slots, max_len, block_size, hd, kv_heads)
    if use_cache:
        hit = _load_cache().get(
            paged_attn_cache_key(n_slots, max_len, block_size, hd, kv_heads))
        if hit:
            bl.update(hit)
    if overrides:
        ov = {k_: int(v) for k_, v in overrides.items() if v is not None}
        unknown = set(ov) - {"block_h"}
        if unknown:
            raise TypeError(f"unknown paged_attn override(s): {sorted(unknown)}")
        bl.update(ov)
    return _clamp_paged(kv_heads, hd, bl)


def measured_paged_blocks(
    n_slots: int,
    max_len: int,
    block_size: int,
    hd: int,
    kv_heads: int,
    *,
    n_heads: Optional[int] = None,
    candidates=None,
    iters: int = 3,
    warmup: int = 1,
    interpret: Optional[bool] = None,
    seed: int = 0,
) -> Dict[str, int]:
    """Time the fused kernel on a synthetic pool over the block_h divisors of
    kv_heads; persist + return the winner (same on-disk cache as
    ``measured_blocks``)."""
    import time

    import jax.numpy as jnp

    from . import ops  # deferred: ops imports this module

    hq = n_heads or kv_heads
    t = max_len // block_size
    n_phys = n_slots * t + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (n_slots, 1, hq, hd), jnp.float32)
    lanes = pool_row_lanes(kv_heads, hd)
    k = jax.random.normal(ks[1], (n_phys, block_size, lanes), jnp.float32)
    v = jax.random.normal(ks[2], (n_phys, block_size, lanes), jnp.float32)
    import numpy as np

    tables = jnp.asarray(
        np.arange(n_slots * t, dtype=np.int32).reshape(n_slots, t))
    q_pos = jnp.full((n_slots, 1), max(3 * max_len // 4 - 1, 0), jnp.int32)

    if candidates is None:
        candidates = sorted({legal_block_h(kv_heads, hd, bh)
                             for bh in range(1, kv_heads + 1)})
    best, best_t = None, float("inf")
    timings = []
    for bh in candidates:
        cl = _clamp_paged(kv_heads, hd, {"block_h": bh})
        fn = lambda: ops.paged_attention(q, k, v, tables, q_pos, n_kv_heads=kv_heads,
                                         interpret=interpret, **cl)
        try:
            for _ in range(warmup):
                jax.block_until_ready(fn())
            t0 = time.perf_counter()
            for _ in range(iters):
                jax.block_until_ready(fn())
            dt = (time.perf_counter() - t0) / iters
        except Exception:
            continue
        timings.append((cl, dt))
        if dt < best_t:
            best, best_t = cl, dt
    if best is None:
        best = heuristic_paged_blocks(n_slots, max_len, block_size, hd, kv_heads)
    _store_cache(paged_attn_cache_key(n_slots, max_len, block_size, hd, kv_heads), best)
    _trace_search(
        f"{PAGED_ATTN_PATH}:{n_slots}x{max_len}x{block_size}x{hd}x{kv_heads}",
        best, best_t, timings)
    return best
