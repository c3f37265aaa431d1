"""Pallas TPU kernels for the BiKA Comparison-Accumulate (CAC) contraction.

The FPGA systolic array (paper Fig. 9) streams activations through a grid of
weight-stationary comparator PEs. The TPU adaptation (DESIGN.md §2) re-tiles
that dataflow for the HBM->VMEM->VREG hierarchy:

  * grid (M/bm, N/bn, K/bk); the (bk, bn) threshold block stays resident in
    VMEM while activation blocks stream over the k-grid — "threshold-block-
    stationary", the BlockSpec rendition of weight-stationary systolic flow;
  * inside a block, the bk inputs are walked in read windows (kernels/
    beats.py: 128 lanes, or the whole block when 128 does not divide it),
    each read from the refs at a tile-aligned offset (``x_ref[:, pl.ds(k0,
    win)]``) and cut into ``bk_sub``-wide beats by static slices — Mosaic
    lowers neither a dynamic slice of a value nor an unaligned dynamic lane
    offset. Each beat materializes a whole (bm, bk_sub, bn) broadcast-
    compare and reduces it on the VPU — a *vectorized* systolic beat
    (bk_sub comparator waves issued as one fused compare-select-reduce).
    ``bk_sub`` is the largest sublane-aligned divisor of the window whose
    sub-tile fits the working-set budget (autotune.pick_block_k_sub);
  * the out block accumulates across the k-grid (k innermost), so partial
    sums never round-trip to HBM.

Backward (training STE) kernels recompute the hard-tanh mask blockwise from
(x, w, beta) — the (M, K, N) mask tensor NEVER materializes, which is the
whole point: at LM scale it would be ~10^12 elements. The one-pass
``cac_train_bwd_fused_call`` produces (dx, dw, dbeta) from a *single* mask
recompute per block; the split dx / dw calls remain for A/B benchmarking.

All kernels run under interpret=True on CPU (how tests validate them) and
compile to Mosaic on TPU (tests/test_tpu_compile.py compiles the serve kernel
for a described v5e at smollm-360m widths).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .autotune import pick_block_k_sub
from .beats import beat_slices, beat_window, fold_windows

__all__ = [
    "cac_matmul_kernel_call",
    "cac_train_fwd_call",
    "cac_train_bwd_dx_call",
    "cac_train_bwd_dw_call",
    "cac_train_bwd_fused_call",
]


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


# ---------------------------------------------------------------------------
# Hardware-form forward: y[m,n] = sum_k s[k,n] * Thres(x[m,k] - tau[k,n])
# ---------------------------------------------------------------------------


def _cac_fwd_kernel(x_ref, tau_ref, s_ref, o_ref, *, bk_sub: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, acc):
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)  # (bm, win)
        tau = tau_ref[pl.ds(k0, win), :].astype(jnp.float32)  # (win, bn)
        s = s_ref[pl.ds(k0, win), :].astype(jnp.float32)
        for sl in beat_slices(win, bk_sub):
            # one vectorized beat: bk_sub comparator waves as a single
            # (bm, bk_sub, bn) broadcast-compare + select, reduced over k_sub
            xs, ts, ss = x[:, sl], tau[sl], s[sl]
            cmp = xs[:, :, None] >= ts[None]
            acc = acc + jnp.sum(jnp.where(cmp, ss[None], -ss[None]), axis=1)
        return acc

    acc = fold_windows(bk, win, window, jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc.astype(o_ref.dtype)


def cac_matmul_kernel_call(
    x: jax.Array,
    tau: jax.Array,
    s: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    block_k_sub: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K); tau, s: (K, N) -> (M, N) float32. Shapes must divide blocks
    (ops.py pads with s == 0 rows, which contribute exactly zero)."""
    m, k = x.shape
    _, n = tau.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0, (m, k, n, bm, bk, bn)
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_cac_fwd_kernel, bk_sub=bks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, tau, s)


# ---------------------------------------------------------------------------
# Training-form forward: y[m,n] = sum_k Sign(x[m,k] w[k,n] + beta[k,n])
# ---------------------------------------------------------------------------


def _cac_train_fwd_kernel(x_ref, w_ref, b_ref, o_ref, *, bk_sub: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, acc):
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)
        w = w_ref[pl.ds(k0, win), :].astype(jnp.float32)
        b = b_ref[pl.ds(k0, win), :].astype(jnp.float32)
        for sl in beat_slices(win, bk_sub):
            pre = x[:, sl][:, :, None] * w[sl][None] + b[sl][None]  # (bm, bk_sub, bn)
            acc = acc + jnp.sum(jnp.where(pre >= 0, 1.0, -1.0), axis=1)
        return acc

    acc = fold_windows(bk, win, window, jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc.astype(o_ref.dtype)


def cac_train_fwd_call(
    x, w, beta, *, block_m=256, block_n=256, block_k=512,
    block_k_sub: Optional[int] = None, interpret=False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_cac_train_fwd_kernel, bk_sub=bks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w, beta)


# ---------------------------------------------------------------------------
# Training-form backward (STE): blockwise mask recomputation
# ---------------------------------------------------------------------------


def _cac_bwd_dx_kernel(x_ref, w_ref, b_ref, g_ref, dx_ref, *, bk_sub: int):
    """dx[m,k] = sum_n g[m,n] * 1[|pre| <= 1] * w[k,n]; accumulates over the
    n-grid (innermost)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    g = g_ref[...].astype(jnp.float32)  # (bm, bn)
    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, carry):
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)  # (bm, win)
        w = w_ref[pl.ds(k0, win), :].astype(jnp.float32)  # (win, bn)
        b = b_ref[pl.ds(k0, win), :].astype(jnp.float32)
        cols = []
        for sl in beat_slices(win, bk_sub):
            ws = w[sl]
            pre = x[:, sl][:, :, None] * ws[None] + b[sl][None]  # (bm, bk_sub, bn)
            mask = jnp.abs(pre) <= 1.0
            # effective weight block (the MXU-able Ŵ of DESIGN.md §2)
            cols.append(jnp.sum(jnp.where(mask, g[:, None, :] * ws[None], 0.0),
                                axis=2))  # (bm, bk_sub)
        dx_ref[:, pl.ds(k0, win)] += jnp.concatenate(cols, axis=1).astype(dx_ref.dtype)
        return carry

    fold_windows(bk, win, window, 0)


def cac_train_bwd_dx_call(
    x, w, beta, g, *, block_m=256, block_n=256, block_k=512,
    block_k_sub: Optional[int] = None, interpret=False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    grid = (m // bm, k // bk, n // bn)  # n innermost: dx block accumulates
    return pl.pallas_call(
        functools.partial(_cac_bwd_dx_kernel, bk_sub=bks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(x, w, beta, g)


def _cac_bwd_dw_kernel(x_ref, w_ref, b_ref, g_ref, dw_ref, db_ref, *, bk_sub: int):
    """dw[k,n] = sum_m g*mask*x; dbeta[k,n] = sum_m g*mask. Accumulates over
    the m-grid (innermost)."""

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    g = g_ref[...].astype(jnp.float32)
    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, carry):
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)  # (bm, win)
        w = w_ref[pl.ds(k0, win), :].astype(jnp.float32)  # (win, bn)
        b = b_ref[pl.ds(k0, win), :].astype(jnp.float32)
        dw_rows, db_rows = [], []
        for sl in beat_slices(win, bk_sub):
            xs = x[:, sl]  # (bm, bk_sub)
            pre = xs[:, :, None] * w[sl][None] + b[sl][None]
            gm = jnp.where(jnp.abs(pre) <= 1.0, g[:, None, :], 0.0)  # (bm,bk_sub,bn)
            db_rows.append(jnp.sum(gm, axis=0))  # (bk_sub, bn)
            dw_rows.append(jnp.sum(gm * xs[:, :, None], axis=0))
        dw_ref[pl.ds(k0, win), :] += jnp.concatenate(dw_rows, 0).astype(dw_ref.dtype)
        db_ref[pl.ds(k0, win), :] += jnp.concatenate(db_rows, 0).astype(db_ref.dtype)
        return carry

    fold_windows(bk, win, window, 0)


def cac_train_bwd_dw_call(
    x, w, beta, g, *, block_m=256, block_n=256, block_k=512,
    block_k_sub: Optional[int] = None, interpret=False,
) -> Tuple[jax.Array, jax.Array]:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    grid = (k // bk, n // bn, m // bm)  # m innermost: dw/db blocks accumulate
    return pl.pallas_call(
        functools.partial(_cac_bwd_dw_kernel, bk_sub=bks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda kk, j, i: (i, kk)),
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
            pl.BlockSpec((bm, bn), lambda kk, j, i: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, beta, g)


# ---------------------------------------------------------------------------
# One-pass fused backward: (dx, dw, dbeta) from a single mask recompute
# ---------------------------------------------------------------------------


def _cac_bwd_fused_kernel(
    x_ref, w_ref, b_ref, g_ref, dx_ref, dw_ref, db_ref, *, bk_sub: int
):
    """Grid (M/bm, K/bk, N/bn), n innermost. Per step the hard-tanh mask is
    recomputed ONCE and feeds all three gradients — vs. twice across the
    split dx/dw calls. dx blocks accumulate over the consecutive n-grid.
    dw/dbeta blocks are each visited once per m-step; Mosaic only guarantees
    output-window carry-over across CONSECUTIVE same-index steps, so this
    kernel requires a single m-block (M <= block_m) — then every dw/dbeta
    block is visited exactly once and dx accumulates innermost. ops.py
    enforces the guard and falls back to the two-call path otherwise."""
    i, j = pl.program_id(0), pl.program_id(2)

    @pl.when(j == 0)
    def _init_dx():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    @pl.when(i == 0)
    def _init_dw():
        dw_ref[...] = jnp.zeros_like(dw_ref)
        db_ref[...] = jnp.zeros_like(db_ref)

    g = g_ref[...].astype(jnp.float32)  # (bm, bn)
    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, carry):
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)  # (bm, win)
        w = w_ref[pl.ds(k0, win), :].astype(jnp.float32)  # (win, bn)
        b = b_ref[pl.ds(k0, win), :].astype(jnp.float32)
        dx_cols, dw_rows, db_rows = [], [], []
        for sl in beat_slices(win, bk_sub):
            xs, ws = x[:, sl], w[sl]
            pre = xs[:, :, None] * ws[None] + b[sl][None]  # (bm, bk_sub, bn)
            gm = jnp.where(jnp.abs(pre) <= 1.0, g[:, None, :], 0.0)
            dx_cols.append(jnp.sum(gm * ws[None], axis=2))  # (bm, bk_sub)
            dw_rows.append(jnp.sum(gm * xs[:, :, None], axis=0))  # (bk_sub, bn)
            db_rows.append(jnp.sum(gm, axis=0))  # (bk_sub, bn)
        dx_ref[:, pl.ds(k0, win)] += jnp.concatenate(dx_cols, 1).astype(dx_ref.dtype)
        dw_ref[pl.ds(k0, win), :] += jnp.concatenate(dw_rows, 0).astype(dw_ref.dtype)
        db_ref[pl.ds(k0, win), :] += jnp.concatenate(db_rows, 0).astype(db_ref.dtype)
        return carry

    fold_windows(bk, win, window, 0)


def cac_train_bwd_fused_call(
    x, w, beta, g, *, block_m=256, block_n=256, block_k=256,
    block_k_sub: Optional[int] = None, interpret=False,
) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """One pallas_call -> (dx, dw, dbeta). Same padding contract as the split
    calls: padded regions have x = 0 and g = 0, so their gradients vanish.

    Requires M <= block_m (single m-block; see kernel docstring). Interpret
    mode tolerates multiple m-blocks (the emulator round-trips output
    windows), which tests exploit, but compiled TPU callers must not."""
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    assert interpret or m == bm, (
        f"fused backward needs a single m-block on TPU (M={m} > block_m={bm})"
    )
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    grid = (m // bm, k // bk, n // bn)  # n innermost: dx accumulates in VMEM
    return pl.pallas_call(
        functools.partial(_cac_bwd_fused_kernel, bk_sub=bks),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
        ],
        out_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, k), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
            jax.ShapeDtypeStruct((k, n), jnp.float32),
        ],
        interpret=interpret,
    )(x, w, beta, g)
