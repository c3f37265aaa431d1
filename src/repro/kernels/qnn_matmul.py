"""Pallas kernel for the QNN baseline: int8 x int8 -> int32 matmul with
fused per-column dequantization (the FINN-R threshold-requant collapses to a
scale on TPU). int8 operands double MXU throughput (394 TOPS on v5e) and
halve HBM traffic vs bf16 — this kernel is the serving path of the QNN
comparison rows in the Table III analogue.

Same schedule treatment as the CAC stack (DESIGN.md §2/§3): grid
(M/bm, N/bn, K/bk) with the k-grid innermost accumulating into a VMEM fp32
block. Inside a block the K extent is read from the refs in tile-aligned
windows (kernels/beats.py) and contracted ``bk_sub`` rows per beat as int8
operands with an int32 MXU accumulator.
Blocks come from kernels/autotune.py (path ``qnn8``) and every caller
accepts explicit overrides.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .autotune import pick_block_k_sub
from .beats import beat_slices, beat_window, fold_windows

__all__ = ["qnn_matmul_kernel_call"]


def _qnn_kernel(x_ref, w_ref, scale_ref, o_ref, *, bk_sub: int, n_k: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    bk = x_ref.shape[1]
    win = beat_window(bk)

    def window(k0, acc):
        x = x_ref[:, pl.ds(k0, win)]  # (bm, win) int8
        w = w_ref[pl.ds(k0, win), :]  # (win, bn) int8
        for sl in beat_slices(win, bk_sub):
            acc = acc + jnp.dot(x[:, sl], w[sl], preferred_element_type=jnp.int32)
        return acc

    acc = fold_windows(bk, win, window, jnp.zeros(o_ref.shape, jnp.int32))
    o_ref[...] += acc.astype(jnp.float32)

    @pl.when(pl.program_id(2) == n_k - 1)
    def _dequant():
        o_ref[...] = o_ref[...] * scale_ref[...]


def qnn_matmul_kernel_call(
    x_int: jax.Array,
    w_int: jax.Array,
    w_scale: jax.Array,
    x_scale: float,
    *,
    block_m: int = 256,
    block_n: int = 256,
    block_k: int = 512,
    block_k_sub: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """x_int: (M, K) int8; w_int: (K, N) int8; w_scale: (1, N) fp32."""
    m, k = x_int.shape
    _, n = w_int.shape
    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    bks = pick_block_k_sub(bm, bn, bk, block_k_sub)
    n_k = k // bk
    scale = (w_scale.reshape(1, n) * jnp.float32(x_scale)).astype(jnp.float32)
    return pl.pallas_call(
        functools.partial(_qnn_kernel, bk_sub=bks, n_k=n_k),
        grid=(m // bm, n // bn, n_k),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        interpret=interpret,
    )(x_int, w_int, scale)
