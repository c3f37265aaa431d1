"""K-beat walking shared by the matmul kernels.

Mosaic (the TPU Pallas compiler) does not lower ``dynamic_slice`` of a value,
and a dynamic slice of a ref must start on a tile boundary: a multiple of 128
on the lane (last) axis. So every kernel walks its block's K extent in
*windows*: ``fold_windows`` hands the body the start of each window, the body
reads the window from its refs (``x_ref[:, pl.ds(k0, window)]``) and then
cuts it into ``bk_sub``-wide beats with static slices. A block whose K extent
is not a multiple of 128 is one window, read at the static offset 0.
"""
from __future__ import annotations

import jax
from jax.experimental import pallas as pl

__all__ = ["LANE", "beat_window", "beat_slices", "fold_windows"]

LANE = 128  # TPU vreg lane width: dynamic lane offsets must be multiples


def beat_window(bk: int, align: int = LANE) -> int:
    """Width of one ref-read window over a block's K extent: ``align`` when
    it divides ``bk`` (dynamic, tile-aligned starts), else the whole block
    (one window at static offset 0)."""
    return align if bk % align == 0 else bk


def fold_windows(bk: int, window: int, body, carry):
    """``carry = body(k0, carry)`` for each window start ``k0`` of a block
    with K extent ``bk``; ``k0`` is a traced multiple of ``window`` inside a
    ``fori_loop``, or the static 0 when there is a single window."""
    n = bk // window
    if n == 1:
        return body(0, carry)
    return jax.lax.fori_loop(
        0, n, lambda i, c: body(pl.multiple_of(i * window, window), c), carry)


def beat_slices(window: int, bk_sub: int):
    """Static slices cutting one read window into its bk_sub-wide beats."""
    return [slice(t, t + bk_sub) for t in range(0, window, bk_sub)]
