"""Pallas kernels for the BNN baseline: XNOR-popcount contraction, lifted to
the same treatment as the CAC stack (sub-tiled beats, autotuned blocks, a
SignSTE backward pair, and a packed-bitplane serve forward).

On FPGA this is LUT XNORs + a popcount tree (FINN). On TPU the identity
popcount2(a XNOR b) - K == dot(sign(a), sign(b)) routes the whole layer onto
the MXU — the contrast with BiKA's VPU-bound compare is exactly the hardware-
adaptation argument of DESIGN.md §2 (multipliers are free here, comparators
are not; the paper's resource ranking inverts).

Schedules (mirroring cac_matmul.py):

  * forward — grid (M/bm, N/bn, K/bk), k innermost, fp32 VMEM accumulator;
    inside a block a fori_loop contracts ``bk_sub`` rows per beat, so only
    the (bm, bk_sub) + (bk_sub, bn) *sign* tiles are live in VREGs per beat
    instead of sign-materializing the whole (bm, bk) x (bk, bn) block.
  * packed forward — weights arrive as uint8 bitplanes ((K/8, N): the serve
    form, 8x less weight HBM traffic); each beat slices whole bitplane rows
    (bk_sub % 8 == 0), unpacks them in VREGs, and feeds the same MXU dot.
  * backward (SignSTE) — two masked MXU contractions, each sub-tiled along
    its *own* contraction axis:
      dx[m,k] = (sum_n g[m,n] sign(w)[k,n]) * 1[|x[m,k]| <= 1]   (contract N)
      dw[k,n] = (sum_m sign(x)[m,k] g[m,n]) * 1[|w[k,n]| <= 1]   (contract M)
    The hard-tanh masks depend only on the output block's own operand, so
    they are applied once on the final accumulation step — the blockwise
    analogue of the CAC stack's mask-recompute backward (no (M, K) / (K, N)
    mask tensors round-trip through HBM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .autotune import pick_block_k_sub
from .beats import beat_slices, beat_window, fold_windows

__all__ = [
    "PACKED_WINDOW",
    "bnn_matmul_kernel_call",
    "bnn_packed_matmul_kernel_call",
    "bnn_bwd_dx_call",
    "bnn_bwd_dw_call",
]


# K rows per read window of the packed kernel: 32 uint8 bitplane rows, one
# whole int8 sublane tile, so each window starts on a tile row.
PACKED_WINDOW = 256


def _sgn(a: jax.Array) -> jax.Array:
    return jnp.where(a >= 0, 1.0, -1.0).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Forward: y = sign(x) @ sign(w)
# ---------------------------------------------------------------------------


def _bnn_kernel(x_ref, w_ref, o_ref, *, bk_sub: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, acc):
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)  # (bm, win)
        w = w_ref[pl.ds(k0, win), :].astype(jnp.float32)  # (win, bn)
        for sl in beat_slices(win, bk_sub):
            acc = acc + jnp.dot(_sgn(x[:, sl]), _sgn(w[sl]),
                                preferred_element_type=jnp.float32)
        return acc

    acc = fold_windows(bk, win, window, jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc.astype(o_ref.dtype)


def bnn_matmul_kernel_call(
    x: jax.Array,
    w: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    block_k_sub: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    # padding note (ops.py): a padded x column is 0 -> sign 0 >= 0 -> +1 on
    # both operands, so each padded K row adds +1; ops.bnn_matmul subtracts
    # the constant after the call.
    return pl.pallas_call(
        functools.partial(_bnn_kernel, bk_sub=bks),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, w)


# ---------------------------------------------------------------------------
# Packed-bitplane serve forward: y = sign(x) @ unpack(wp)
# ---------------------------------------------------------------------------


def _bnn_packed_kernel(x_ref, wp_ref, o_ref, *, bk_sub: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    bn = wp_ref.shape[1]
    # 256 unpacked rows = 32 packed uint8 rows: one full int8 sublane tile
    win = beat_window(bk, PACKED_WINDOW)
    shifts = jax.lax.broadcasted_iota(jnp.int32, (1, 8, 1), 1)

    def window(k0, acc):
        p0 = k0 // 8 if isinstance(k0, int) else pl.multiple_of(k0 // 8, win // 8)
        x = x_ref[:, pl.ds(k0, win)].astype(jnp.float32)  # (bm, win)
        wp = wp_ref[pl.ds(p0, win // 8), :].astype(jnp.int32)  # (win/8, bn)
        for sl in beat_slices(win, bk_sub):
            rows = wp[sl.start // 8:sl.stop // 8]  # (bk_sub/8, bn) bitplanes
            bits = (rows[:, None, :] >> shifts) & 1  # (bk_sub/8, 8, bn)
            ws = (2.0 * bits.reshape(bk_sub, bn).astype(jnp.float32)) - 1.0
            acc = acc + jnp.dot(_sgn(x[:, sl]), ws,
                                preferred_element_type=jnp.float32)
        return acc

    acc = fold_windows(bk, win, window, jnp.zeros(o_ref.shape, jnp.float32))
    o_ref[...] += acc.astype(o_ref.dtype)


def bnn_packed_matmul_kernel_call(
    x: jax.Array,
    wp: jax.Array,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    block_k_sub: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """x: (M, K) float; wp: (K/8, N) uint8 bitplanes (bit j = edge k%8==j).

    The K grid/beat structure runs in units of *unpacked* rows; bk and
    bk_sub are therefore multiples of 8 (the caller pads K accordingly).
    A zero pad byte unpacks to eight -1 weights against sign(0) = +1
    activations, so each padded K row contributes -1; ops.bnn_matmul_packed
    adds the constant back."""
    m, k = x.shape
    k8, n = wp.shape
    assert k == 8 * k8, (x.shape, wp.shape)
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert bk % 8 == 0, f"packed path needs block_k % 8 == 0, got {bk}"
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub, align=PACKED_WINDOW)
    assert bks % 8 == 0 and bk % bks == 0
    return pl.pallas_call(
        functools.partial(_bnn_packed_kernel, bk_sub=bks),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk // 8, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x, wp)


# ---------------------------------------------------------------------------
# SignSTE backward pair
# ---------------------------------------------------------------------------


def _bnn_bwd_dx_kernel(x_ref, w_ref, g_ref, dx_ref, *, bn_sub: int, n_j: int):
    """dx = (g @ sign(w).T) * 1[|x| <= 1]; grid (M/bm, K/bk, N/bn), n
    innermost accumulating; the x-mask is applied on the last n step."""
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dx_ref[...] = jnp.zeros_like(dx_ref)

    bn = w_ref.shape[1]
    win = beat_window(bn)

    def window(n0, acc):
        g = g_ref[:, pl.ds(n0, win)].astype(jnp.float32)  # (bm, win)
        w = w_ref[:, pl.ds(n0, win)].astype(jnp.float32)  # (bk, win)
        for sl in beat_slices(win, bn_sub):
            acc = acc + jnp.dot(g[:, sl], _sgn(w[:, sl]).T,
                                preferred_element_type=jnp.float32)
        return acc

    acc = fold_windows(bn, win, window, jnp.zeros(dx_ref.shape, jnp.float32))
    dx_ref[...] += acc.astype(dx_ref.dtype)

    @pl.when(j == n_j - 1)
    def _mask():
        x = x_ref[...].astype(jnp.float32)  # (bm, bk)
        dx_ref[...] = jnp.where(jnp.abs(x) <= 1.0, dx_ref[...], 0.0)


def bnn_bwd_dx_call(
    x, w, g, *, block_m=256, block_n=256, block_k=256,
    block_n_sub: Optional[int] = None, interpret=False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    # the beat contracts N here: pick the sub-tile along bn
    bns = pick_block_k_sub(bm, bk, bn, block_n_sub)
    grid = (m // bm, k // bk, n // bn)  # n innermost: dx block accumulates
    return pl.pallas_call(
        functools.partial(_bnn_bwd_dx_kernel, bn_sub=bns, n_j=n // bn),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, kk, j: (kk, j)),
            pl.BlockSpec((bm, bn), lambda i, kk, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, kk, j: (i, kk)),
        out_shape=jax.ShapeDtypeStruct((m, k), jnp.float32),
        interpret=interpret,
    )(x, w, g)


def _bnn_bwd_dw_kernel(x_ref, w_ref, g_ref, dw_ref, *, bm_sub: int, n_i: int):
    """dw = (sign(x).T @ g) * 1[|w| <= 1]; grid (K/bk, N/bn, M/bm), m
    innermost accumulating; the w-mask is applied on the last m step."""
    i = pl.program_id(2)

    @pl.when(i == 0)
    def _init():
        dw_ref[...] = jnp.zeros_like(dw_ref)

    bm = x_ref.shape[0]
    win = beat_window(bm)

    def window(m0, acc):
        x = x_ref[pl.ds(m0, win), :].astype(jnp.float32)  # (win, bk)
        g = g_ref[pl.ds(m0, win), :].astype(jnp.float32)  # (win, bn)
        for sl in beat_slices(win, bm_sub):
            acc = acc + jnp.dot(_sgn(x[sl]).T, g[sl],
                                preferred_element_type=jnp.float32)
        return acc

    acc = fold_windows(bm, win, window, jnp.zeros(dw_ref.shape, jnp.float32))
    dw_ref[...] += acc.astype(dw_ref.dtype)

    @pl.when(i == n_i - 1)
    def _mask():
        w = w_ref[...].astype(jnp.float32)  # (bk, bn)
        dw_ref[...] = jnp.where(jnp.abs(w) <= 1.0, dw_ref[...], 0.0)


def bnn_bwd_dw_call(
    x, w, g, *, block_m=256, block_n=256, block_k=256,
    block_m_sub: Optional[int] = None, interpret=False,
) -> jax.Array:
    m, k = x.shape
    _, n = w.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    # the beat contracts M here: pick the sub-tile along bm
    bms = pick_block_k_sub(bk, bn, bm, block_m_sub)
    grid = (k // bk, n // bn, m // bm)  # m innermost: dw block accumulates
    return pl.pallas_call(
        functools.partial(_bnn_bwd_dw_kernel, bm_sub=bms, n_i=m // bm),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda kk, j, i: (i, kk)),
            pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
            pl.BlockSpec((bm, bn), lambda kk, j, i: (i, j)),
        ],
        out_specs=pl.BlockSpec((bk, bn), lambda kk, j, i: (kk, j)),
        out_shape=jax.ShapeDtypeStruct((k, n), jnp.float32),
        interpret=interpret,
    )(x, w, g)
