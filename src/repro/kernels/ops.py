"""jit-able public wrappers around the Pallas kernels: batch-dim flattening,
block padding (with exact zero-contribution padding schemes per kernel), and
the custom-VJP training op ``cac_train_matmul`` whose backward runs the
blockwise mask-recompute kernels (no (M,K,N) residual — DESIGN.md §2).

Block sizes are resolved per call site by ``autotune.get_blocks`` (heuristic
table + optional measured cache), and every wrapper — including
``cac_train_matmul`` — accepts explicit ``**blocks`` overrides
(``block_m`` / ``block_n`` / ``block_k`` / ``block_k_sub``).

``interpret=None`` auto-selects interpret mode off-TPU, so the same call
sites run on CPU tests (the Pallas interpreter) and compile through Mosaic on
a TPU.

Tensor parallelism: when the active mesh (``jax.set_mesh``, entered by the
serving engine through ``distributed.constraints.mesh_context``) carries a
``model`` axis of size > 1, the SERVE-path wrappers (``cac_matmul`` /
``bnn_matmul`` / ``bnn_matmul_packed`` / ``qnn_matmul``) route the
contraction through ``jax.shard_map`` column-parallel: weights split on their
output (N) dim, activations replicated, each device running the unmodified
kernel on its N-shard. No cross-device reduction is introduced, so per-column
sums keep the exact single-device accumulation order — sharded outputs are
bit-identical to the unsharded kernel. When N (or, for ``paged_attention``,
a head count) does not divide the model axis, the wrapper falls back to the
pure-XLA reference (kernels/ref.py), which GSPMD partitions freely. That
fallback is logged once per route and shape, and ``tp_dispatch_log()`` lists
every decision taken. Training routes keep plain GSPMD partitioning
(shard_map + custom VJP replication bookkeeping is not worth it for paths the
trainer already shards well).
"""
from __future__ import annotations

import functools
import logging
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from . import autotune, ref
from .bnn_matmul import (
    bnn_bwd_dw_call,
    bnn_bwd_dx_call,
    bnn_matmul_kernel_call,
    bnn_packed_matmul_kernel_call,
)
from .cac_matmul import (
    cac_matmul_kernel_call,
    cac_train_bwd_dw_call,
    cac_train_bwd_dx_call,
    cac_train_bwd_fused_call,
    cac_train_fwd_call,
)
from .paged_attn import paged_attn_kernel_call
from .qnn_matmul import qnn_matmul_kernel_call

__all__ = [
    "cac_matmul",
    "cac_train_matmul",
    "bnn_matmul",
    "bnn_matmul_packed",
    "bnn_train_matmul",
    "qnn_matmul",
    "paged_attention",
    "KERNEL_ROUTES",
    "kernel_route",
    "tp_dispatch_log",
]

log = logging.getLogger(__name__)

# Default for the one-pass fused STE backward; the two-call path stays
# reachable via cac_train_matmul(..., fused_bwd=False) for A/B benchmarking.
FUSED_BWD_DEFAULT = True


def _auto_interpret(interpret: Optional[bool]) -> bool:
    if interpret is None:
        return jax.default_backend() != "tpu"
    return interpret


# ---------------------------------------------------------------------------
# Tensor-parallel dispatch (serve paths)
# ---------------------------------------------------------------------------

TP_AXIS = "model"


def _tp_mesh():
    """The active mesh when it tensor-parallelizes (model axis > 1), else
    None. Read from ``jax.sharding.get_abstract_mesh()`` — call sites inside
    jit pick it up from the caller's ``mesh_context`` (jax.set_mesh)."""
    from repro.distributed.constraints import _context_mesh

    mesh = _context_mesh()
    if mesh is None or int(mesh.shape.get(TP_AXIS, 1)) <= 1:
        return None
    return mesh


# (route, operand shapes) -> "shard_map" | "xla_fallback", one entry per TP
# dispatch decision (made while tracing, so once per jitted shape)
_TP_DISPATCH: Dict[Tuple[str, Tuple], str] = {}


def _note_tp_dispatch(route: str, shapes: Tuple, taken: str, why: str = "") -> None:
    key = (route, shapes)
    if key in _TP_DISPATCH:
        return
    _TP_DISPATCH[key] = taken
    if taken == "xla_fallback":
        log.warning("tensor-parallel %s at %s runs the XLA reference, not the "
                    "kernel: %s", route, shapes, why)


def tp_dispatch_log() -> Dict[Tuple[str, Tuple], str]:
    """Every tensor-parallel route decision made so far in this process:
    ``{(route, operand shapes): "shard_map" | "xla_fallback"}``."""
    return dict(_TP_DISPATCH)


def _tp_shard_call(route: str, impl, ref_impl, x2: jax.Array,
                   weights: Tuple[jax.Array, ...], n: int) -> jax.Array:
    """Run ``impl(x2, *weights) -> (M, N)`` column-parallel over the model
    axis when a TP mesh is active: every weight operand splits on its last
    (N) dim, ``x2`` is replicated, and the output stays N-sharded for the
    next layer to consume. Each shard runs the unmodified Pallas kernel on
    its (M, K, N/tp) slice — no reduction is split, so the result is
    bit-identical to the single-device kernel. Falls back to ``ref_impl``
    (pure XLA, GSPMD-partitionable) when N does not divide the axis, and
    logs that fallback once per route and shape."""
    mesh = _tp_mesh()
    if mesh is None:
        return impl(x2, *weights)
    tp = int(mesh.shape[TP_AXIS])
    shapes = (x2.shape,) + tuple(w.shape for w in weights)
    if n % tp != 0:
        _note_tp_dispatch(route, shapes, "xla_fallback",
                          f"N={n} does not divide the model axis ({tp})")
        return ref_impl(x2, *weights)
    _note_tp_dispatch(route, shapes, "shard_map")
    wspec = PartitionSpec(None, TP_AXIS)
    fn = jax.shard_map(
        impl,
        mesh=mesh,
        in_specs=(PartitionSpec(),) + (wspec,) * len(weights),
        out_specs=PartitionSpec(None, TP_AXIS),
        check_vma=False,
    )
    return fn(x2, *weights)


def _round_up(v: int, b: int) -> int:
    return -(-v // b) * b


def _pad_axis(a: jax.Array, axis: int, to: int, value=0.0) -> jax.Array:
    pad = to - a.shape[axis]
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths, constant_values=value)


def _resolve_blocks(m, k, n, path, blocks) -> Tuple[int, int, int, Optional[int]]:
    bl = autotune.get_blocks(m, k, n, path, overrides=blocks or None)
    return bl["block_m"], bl["block_n"], bl["block_k"], bl.get("block_k_sub")


def _flatten(x: jax.Array) -> Tuple[jax.Array, Tuple[int, ...]]:
    lead = x.shape[:-1]
    return x.reshape(-1, x.shape[-1]), lead


def _cac_hw_impl(x2, tau, s, *, interpret: bool, blocks) -> jax.Array:
    m, k = x2.shape
    n = tau.shape[1]
    bm, bn, bk, bks = _resolve_blocks(m, k, n, "hw_fwd", blocks)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    x2 = _pad_axis(x2, 0, mp)
    x2 = _pad_axis(x2, 1, kp)
    tau_p = _pad_axis(_pad_axis(tau, 0, kp), 1, np_)
    s_p = _pad_axis(_pad_axis(s, 0, kp, value=0), 1, np_)  # s=0 pad -> zero contribution
    y = cac_matmul_kernel_call(
        x2, tau_p, s_p, block_m=bm, block_n=bn, block_k=bk, block_k_sub=bks,
        interpret=interpret,
    )
    return y[:m, :n]


def cac_matmul(
    x: jax.Array,
    tau: jax.Array,
    s: jax.Array,
    *,
    interpret: Optional[bool] = None,
    **blocks,
) -> jax.Array:
    """Hardware-form CAC. x: (..., K); tau, s: (K, N) -> (..., N) fp32.

    Padding scheme: K rows padded with s = 0 contribute exactly 0; M rows and
    N cols are sliced away after the call. Under an active TP mesh the call
    runs column-parallel via shard_map (see module docstring)."""
    x2, lead = _flatten(x)
    n = tau.shape[1]
    impl = functools.partial(_cac_hw_impl, interpret=_auto_interpret(interpret),
                             blocks=blocks)
    y = _tp_shard_call("cac_hw", impl, ref.cac_matmul_ref, x2, (tau, s), n)
    return y.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# Training op with custom VJP
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _cac_train(x2, w, beta, interpret, fused, blocks):
    return _cac_train_fwd_impl(x2, w, beta, interpret, blocks)[0]


def _cac_train_fwd_impl(x2, w, beta, interpret, blocks):
    m, k = x2.shape
    n = w.shape[1]
    bm, bn, bk, bks = _resolve_blocks(m, k, n, "train_fwd", dict(blocks))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = _pad_axis(_pad_axis(x2, 0, mp), 1, kp)
    wp = _pad_axis(_pad_axis(w, 0, kp), 1, np_)
    bp = _pad_axis(_pad_axis(beta, 0, kp), 1, np_)
    y = cac_train_fwd_call(xp, wp, bp, block_m=bm, block_n=bn, block_k=bk,
                           block_k_sub=bks, interpret=interpret)
    # padded K rows contribute Sign(0*0+0) = +1 each: subtract the constant
    k_pad = kp - k
    y = y[:m, :n]
    if k_pad:
        y = y - jnp.float32(k_pad)
    # residuals are the UNPADDED operands (re-padded in the backward): on
    # ragged shapes the padded copies would pin up to a full extra block per
    # axis of (x, w, beta) in HBM for the whole fwd->bwd interval.
    return y, (x2, w, beta)


def _cac_train_fwd(x2, w, beta, interpret, fused, blocks):
    return _cac_train_fwd_impl(x2, w, beta, interpret, blocks)


def _cac_train_bwd(interpret, fused, blocks, res, g):
    x2, w, beta = res
    m, k = x2.shape
    n = w.shape[1]
    bm, bn, bk, bks = _resolve_blocks(m, k, n, "train_bwd", dict(blocks))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = _pad_axis(_pad_axis(x2, 0, mp), 1, kp)
    wp = _pad_axis(_pad_axis(w, 0, kp), 1, np_)
    bp = _pad_axis(_pad_axis(beta, 0, kp), 1, np_)
    gp = _pad_axis(_pad_axis(g, 0, mp), 1, np_)
    # The fused kernel's dw/dbeta blocks are visited once per m-block; Mosaic
    # only guarantees output-window carry-over across consecutive same-index
    # grid steps, so on compiled TPU the fused path needs a single m-block.
    # Interpret mode (CPU) round-trips output windows and is safe at any nm.
    if fused and not (interpret or mp == bm):
        fused = False
    if fused:
        dx, dw, dbeta = cac_train_bwd_fused_call(
            xp, wp, bp, gp, block_m=bm, block_n=bn, block_k=bk,
            block_k_sub=bks, interpret=interpret,
        )
    else:
        dx = cac_train_bwd_dx_call(xp, wp, bp, gp, block_m=bm, block_n=bn,
                                   block_k=bk, block_k_sub=bks,
                                   interpret=interpret)
        dw, dbeta = cac_train_bwd_dw_call(xp, wp, bp, gp, block_m=bm,
                                          block_n=bn, block_k=bk,
                                          block_k_sub=bks, interpret=interpret)
    # padded regions: g = 0 and x = 0 there, so gradients vanish; just slice.
    return dx[:m, :k], dw[:k, :n], dbeta[:k, :n]


_cac_train.defvjp(_cac_train_fwd, _cac_train_bwd)


def cac_train_matmul(
    x: jax.Array,
    w: jax.Array,
    beta: jax.Array,
    *,
    interpret: Optional[bool] = None,
    fused_bwd: Optional[bool] = None,
    **blocks,
) -> jax.Array:
    """Training CAC with STE backward, Pallas fwd+bwd. x: (..., K) -> (..., N).

    ``fused_bwd=None`` (default) uses the one-pass (dx, dw, dbeta) backward
    kernel; ``False`` selects the legacy two-call backward. ``**blocks``
    overrides the autotuned block sizes, like the sibling wrappers."""
    x2, lead = _flatten(x)
    fused = FUSED_BWD_DEFAULT if fused_bwd is None else fused_bwd
    y = _cac_train(x2.astype(jnp.float32), w.astype(jnp.float32),
                   beta.astype(jnp.float32), _auto_interpret(interpret),
                   fused, tuple(sorted(blocks.items())))
    return y.reshape(lead + (w.shape[1],))


def _bnn_fwd_padded(x2, w, interpret, blocks):
    """Shared forward plumbing for bnn_matmul and the training op."""
    m, k = x2.shape
    n = w.shape[1]
    bm, bn, bk, bks = _resolve_blocks(m, k, n, "bnn", dict(blocks))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = _pad_axis(_pad_axis(x2, 0, mp), 1, kp)
    wp = _pad_axis(_pad_axis(w, 0, kp), 1, np_)
    y = bnn_matmul_kernel_call(xp, wp, block_m=bm, block_n=bn, block_k=bk,
                               block_k_sub=bks, interpret=interpret)
    y = y[:m, :n]
    if kp - k:
        y = y - jnp.float32(kp - k)
    return y


def bnn_matmul(x: jax.Array, w: jax.Array, *, interpret: Optional[bool] = None,
               **blocks) -> jax.Array:
    """sign(x) @ sign(w). Padding: padded K rows give sign(0)=+1 on both
    operands -> each pad row adds +1; subtract the constant. TP meshes run
    it column-parallel (see module docstring)."""
    x2, lead = _flatten(x)
    impl = functools.partial(_bnn_fwd_padded, interpret=_auto_interpret(interpret),
                             blocks=blocks)
    y = _tp_shard_call("bnn", impl, ref.bnn_matmul_ref, x2, (w,), w.shape[1])
    return y.reshape(lead + (w.shape[1],))


def _bnn_packed_impl(x2, wp, *, interpret: bool, blocks) -> jax.Array:
    m, k = x2.shape
    k8, n = wp.shape
    assert k == 8 * k8, f"x K={k} must equal 8 * packed rows ({k8})"
    bm, bn, bk, bks = _resolve_blocks(m, k, n, "bnn", dict(blocks))
    bk = max((min(bk, k) // 8) * 8, 8)  # K grid steps slice whole bytes
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = _pad_axis(_pad_axis(x2, 0, mp), 1, kp)
    wpp = _pad_axis(_pad_axis(wp, 0, kp // 8), 1, np_)
    y = bnn_packed_matmul_kernel_call(
        xp, wpp, block_m=bm, block_n=bn, block_k=bk, block_k_sub=bks,
        interpret=interpret,
    )
    y = y[:m, :n]
    if kp - k:
        y = y + jnp.float32(kp - k)
    return y


def _bnn_packed_ref(x2, wp):
    """XLA fallback for the packed serve kernel: unpack, then the sign-matmul
    reference (bit-exact: ±1 partial sums are integers in fp32)."""
    from repro.core.backend import unpack_signs

    return ref.bnn_matmul_ref(x2, unpack_signs(wp, 8 * wp.shape[0]).astype(jnp.float32))


def bnn_matmul_packed(x: jax.Array, wp: jax.Array, *,
                      interpret: Optional[bool] = None, **blocks) -> jax.Array:
    """sign(x) @ unpack(wp) for uint8 bitplane weights ((K/8, N): the bnn
    serve form). The bitplanes stay packed all the way into VMEM and are
    unpacked per beat in VREGs — 8x less weight HBM traffic than the int8
    route, mirroring the bika packed-serve story.

    Padding: K is padded in units of 8 rows with zero *bytes*; a zero byte
    unpacks to eight -1 weights against sign(0) = +1 activations, so each
    padded K row contributes -1 — add the constant back."""
    x2, lead = _flatten(x)
    n = wp.shape[1]
    impl = functools.partial(_bnn_packed_impl, interpret=_auto_interpret(interpret),
                             blocks=blocks)
    y = _tp_shard_call("bnn_packed", impl, _bnn_packed_ref, x2, (wp,), n)
    return y.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# BNN training op with SignSTE custom VJP (fwd + bwd all on the kernel route)
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def _bnn_train(x2, w, interpret, blocks):
    return _bnn_fwd_padded(x2, w, interpret, blocks)


def _bnn_train_fwd(x2, w, interpret, blocks):
    # residuals are the unpadded float operands; the backward recomputes the
    # sign/mask terms blockwise (no (M, N)-shaped mask tensors in HBM)
    return _bnn_fwd_padded(x2, w, interpret, blocks), (x2, w)


def _bnn_train_bwd(interpret, blocks, res, g):
    x2, w = res
    m, k = x2.shape
    n = w.shape[1]
    bm, bn, bk, _ = _resolve_blocks(m, k, n, "bnn_bwd", dict(blocks))
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = _pad_axis(_pad_axis(x2, 0, mp), 1, kp)
    wp = _pad_axis(_pad_axis(w, 0, kp), 1, np_)
    gp = _pad_axis(_pad_axis(g, 0, mp), 1, np_)
    # padded regions: g = 0 there, so both contractions vanish; just slice.
    dx = bnn_bwd_dx_call(xp, wp, gp, block_m=bm, block_n=bn, block_k=bk,
                         interpret=interpret)
    dw = bnn_bwd_dw_call(xp, wp, gp, block_m=bm, block_n=bn, block_k=bk,
                         interpret=interpret)
    return dx[:m, :k], dw[:k, :n]


_bnn_train.defvjp(_bnn_train_fwd, _bnn_train_bwd)


def bnn_train_matmul(x: jax.Array, w: jax.Array, *,
                     interpret: Optional[bool] = None, **blocks) -> jax.Array:
    """Training BNN with the SignSTE backward on the Pallas route:
    y = sign(x) @ sign(w);  dx = (g @ sign(w)^T) * 1[|x| <= 1];
    dw = (sign(x)^T @ g) * 1[|w| <= 1] — identical semantics to the XLA
    ``sign_ste(x) @ sign_ste(w)`` fallback. x: (..., K) -> (..., N);
    ``**blocks`` overrides the autotuned forward blocks."""
    x2, lead = _flatten(x)
    y = _bnn_train(x2.astype(jnp.float32), w.astype(jnp.float32),
                   _auto_interpret(interpret), tuple(sorted(blocks.items())))
    return y.reshape(lead + (w.shape[1],))


def _qnn_impl(x2, w_int, w_scale, *, x_scale: float, interpret: bool, blocks):
    m, k = x2.shape
    n = w_int.shape[1]
    bm, bn, bk, bks = _resolve_blocks(m, k, n, "qnn8", blocks)
    mp, kp, np_ = _round_up(m, bm), _round_up(k, bk), _round_up(n, bn)
    xp = _pad_axis(_pad_axis(x2, 0, mp), 1, kp)
    wp = _pad_axis(_pad_axis(w_int, 0, kp), 1, np_)
    sp = _pad_axis(w_scale.reshape(1, -1), 1, np_)
    y = qnn_matmul_kernel_call(xp, wp, sp, x_scale, block_m=bm, block_n=bn,
                               block_k=bk, block_k_sub=bks, interpret=interpret)
    return y[:m, :n]


def qnn_matmul(
    x_int: jax.Array,
    w_int: jax.Array,
    w_scale: jax.Array,
    x_scale: float,
    *,
    interpret: Optional[bool] = None,
    **blocks,
) -> jax.Array:
    """int8 matmul + dequant. Zero padding is exact for integer dot. TP
    meshes run it column-parallel (see module docstring)."""
    x2, lead = _flatten(x_int)
    n = w_int.shape[1]
    w_scale = w_scale.reshape(1, -1)  # rank-2 so the TP spec splits its N dim
    impl = functools.partial(_qnn_impl, x_scale=x_scale,
                             interpret=_auto_interpret(interpret), blocks=blocks)
    ref_impl = lambda xi, wi, ws: ref.qnn_matmul_ref(xi, wi, x_scale, ws)
    y = _tp_shard_call("qnn8", impl, ref_impl, x2, (w_int, w_scale), n)
    return y.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# Fused paged attention (serving decode / chunked prefill)
# ---------------------------------------------------------------------------


def paged_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    tables: jax.Array,
    q_pos: jax.Array,
    *,
    n_kv_heads: int,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    interpret: Optional[bool] = None,
    **blocks,
) -> jax.Array:
    """Fused block-table attention (kernels/paged_attn.py): online-softmax
    walk over each row's physical blocks, no gathered KV copy. With
    ``k_scale``/``v_scale`` the int8 pool is dequantized inside the beat.

    q: (B, C, Hq, D); k/v: one lane-dense pool layer (n_phys, bs, lanes)
    holding ``n_kv_heads`` heads side by side from lane 0, scales (n_phys,
    bs, n_kv_heads); tables: (B, T) int32; q_pos: (B, C) int32.
    ``**blocks`` overrides the autotuned ``block_h`` (kv heads per grid
    step; "paged_attn" path).

    Tensor parallelism: attention is embarrassingly parallel over kv-head
    groups, so under an active model-axis mesh the call shard_maps with
    q's head dim and the pool's lane dim split — a contiguous split of
    unpadded rows is a split by head group — and each device runs the
    unmodified kernel on its heads, bit-identical to the unsharded kernel.
    When the head counts don't divide the axis, or the rows are padded, it
    falls back to the pure-XLA gather oracle (kernels/ref.py), which GSPMD
    partitions freely, and logs the fallback once per shape
    (``tp_dispatch_log``)."""
    b, c, hq, d = q.shape
    bs, hkv = k.shape[1], n_kv_heads
    bl = autotune.get_paged_blocks(
        b, tables.shape[1] * bs, bs, d, hkv, overrides=blocks or None)
    impl = functools.partial(paged_attn_kernel_call,
                             block_h=bl["block_h"],
                             interpret=_auto_interpret(interpret))
    oracle = functools.partial(ref.paged_attention_ref, n_kv_heads=hkv)
    mesh = _tp_mesh()
    scales = () if k_scale is None else (k_scale, v_scale)
    if mesh is None:
        return impl(q, k, v, tables, q_pos, n_kv_heads=hkv,
                    k_scale=k_scale, v_scale=v_scale)
    tp = int(mesh.shape[TP_AXIS])
    shapes = (q.shape, k.shape, tables.shape)
    if hq % tp or hkv % tp or k.shape[2] != hkv * d:
        _note_tp_dispatch("paged_attn", shapes, "xla_fallback",
                          f"heads {hq}q/{hkv}kv in pool rows of {k.shape[2]} lanes "
                          f"do not split by head over the model axis ({tp})")
        return oracle(q, k, v, tables, q_pos, k_scale, v_scale)
    _note_tp_dispatch("paged_attn", shapes, "shard_map")
    hspec = PartitionSpec(None, None, TP_AXIS, None)
    lanes = PartitionSpec(None, None, TP_AXIS)  # pool rows: head groups

    def sharded(qs, ks, vs, tbl, qp, *sc):
        ksc, vsc = sc if sc else (None, None)
        return impl(qs, ks, vs, tbl, qp, n_kv_heads=hkv // tp, k_scale=ksc, v_scale=vsc)

    fn = jax.shard_map(
        sharded,
        mesh=mesh,
        in_specs=(hspec, lanes, lanes, PartitionSpec(), PartitionSpec())
        + (lanes,) * len(scales),
        out_specs=hspec,
        check_vma=False,
    )
    return fn(q, k, v, tables, q_pos, *scales)


# ---------------------------------------------------------------------------
# Kernel-route table: the names QuantBackend.kernel_route resolves against
# ---------------------------------------------------------------------------

KERNEL_ROUTES: dict = {
    "cac_hw": cac_matmul,
    "cac_train": cac_train_matmul,
    "bnn": bnn_matmul,
    "bnn_packed": bnn_matmul_packed,
    "bnn_train": bnn_train_matmul,
    "qnn8": qnn_matmul,
    # serving attention (not a matmul route, but resolved the same way:
    # nn/attention.py selects it against the gather fallback per AttnConfig)
    "paged_attn": paged_attention,
}


def kernel_route(name: str):
    """Resolve a route name (from ``QuantBackend.kernel_route``) to its
    jit-able wrapper. Raises KeyError with the known names on a miss."""
    try:
        return KERNEL_ROUTES[name]
    except KeyError:
        raise KeyError(
            f"unknown kernel route {name!r}; known: {sorted(KERNEL_ROUTES)}"
        ) from None
