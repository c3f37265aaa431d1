"""Fused paged-attention Pallas kernel: online-softmax walk over block tables.

The gather-based paged decode (nn/attention.py ``paged_gather``) materializes
every slot's full ``(max_len, kv_heads, hd)`` logical KV window in HBM on
each tick — a dense-cache copy per generated token. This kernel walks the
block table directly instead:

- grid ``(B, n_kv_heads/block_h, T)`` with the table as a *scalar-prefetched*
  operand: step ``(b, h, j)`` streams physical block ``table[b, j]`` of the
  pool into VMEM through a BlockSpec index map — only the blocks a row
  actually names are ever touched, and no gathered copy exists anywhere.
- the pool is read in place: one layer is lane-dense,
  ``(n_phys_blocks, block_size, row_lanes)`` with the kv heads side by side
  and the row padded to whole lane tiles (models/base.PagedKVLayout). The
  TPU stores such an array row-major, so a step's block — the whole row,
  or ``(1, bs, block_h * D)`` for a head group of whole lane tiles — is a
  slab of the pool as it lies, and XLA has no reason to copy the pool into
  another layout around the call. Head ``h`` of the slab is the static
  lane slice ``[h*D, (h+1)*D)``; the padding lanes are never read.
- flash-style online softmax: per (row, kv-head-group) running ``(m, l,
  acc)`` state lives in VMEM scratch, folded block-by-block along the
  innermost grid dim and normalized once on the last block. The final
  partial block (and every pad/future position) is masked per query with
  ``kv_pos <= q_pos`` — for decode that is exactly ``kv_pos < valid_len``.
- fused int8 dequant: with scale operands ``(n_phys_blocks, block_size,
  n_kv_heads)`` the k/v blocks arrive as int8 and are dequantized in-VREG
  inside the beat (``q.astype(f32) * scale``, the same element math as
  nn/attention._dequantize_kv), so the quantized pool is never expanded to
  fp in HBM. A scale block spans every head (its lane dim is whole); the
  beat selects its head's column with an exact masked sum.
- whole-block skip: blocks entirely past every query position of the row
  (``j * bs > max(q_pos)``, the row maximum being a second scalar-prefetch
  operand) skip the compute beat, so decode work scales with each row's
  *actual* context, not ``max_len``.
- GQA without an in-kernel reshape: the wrapper lays q out as ``(B, Hkv,
  g*C, D)`` and the query positions as ``(B, g*C, 1)``, so every block keeps
  its last two dims whole (the (8, 128) rule) and each kv head of a step is
  one 2-D ``(g*C, D) x (bs, D)`` contraction against the head's lane slice
  of the pool block.

One kernel serves both paged call sites: single-token decode is ``C = 1``
with ``q_pos = position`` and chunked prefill is ``C = chunk`` with per-token
logical positions (intra-chunk causality falls out of the same mask).

Numerics: fp32 score/softmax math like dot_attention, but blockwise
accumulation — outputs are within float rounding (~1e-6) of the gather
oracle, not bit-equal; the serving tests pin token-for-token parity. On a
TPU the dots follow the matmul precision in effect: at the default, Mosaic
runs f32 dots as bf16 passes (a few 1e-3 off a "highest" oracle on a v5e),
at "highest" as f32 (~1e-6); chip_smoke.py checks both.

TPU note: a pool block's lane dim ``block_h * D`` must be a multiple of 128
or the whole row, so ``block_h`` is clamped to such a divisor of Hkv
(``legal_block_h``; smollm's 5 x 64 takes all five heads and the row's 384
lanes, phi3's 32 x 96 any multiple of 4); block_size below 8 pads the
sublanes. The heuristic in kernels/autotune.py sizes ``block_h`` so a
step's working set stays inside the sub-tile budget. CPU tests run
``interpret=True``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .beats import LANE

__all__ = ["legal_block_h", "paged_attn_kernel_call", "pool_row_lanes"]

NEG_INF = -1e30  # matches nn/attention.py masking


def pool_row_lanes(n_kv_heads: int, head_dim: int) -> int:
    """Lanes of one paged pool row: every kv head's ``head_dim`` values side
    by side, padded to whole lane tiles (models/base.PagedKVLayout)."""
    return -(-n_kv_heads * head_dim // LANE) * LANE


def legal_block_h(kv_heads: int, hd: int, block_h: int) -> int:
    """The largest kv-head group ``<= block_h`` that divides ``kv_heads`` and
    whose pool slab of ``block_h * hd`` lanes the TPU can block: a multiple
    of 128 lanes, or every head of the row."""
    bh = max(1, min(int(block_h), kv_heads))
    while bh > 1 and (kv_heads % bh or (bh * hd % LANE and bh != kv_heads)):
        bh -= 1
    return bh if bh * hd % LANE == 0 or bh == kv_heads else kv_heads


def _paged_attn_kernel(tbl_ref, qmax_ref, qpos_ref, q_ref, k_ref, v_ref, *rest,
                       bs: int, quantized: bool):
    if quantized:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    del tbl_ref  # consumed by the pool index maps
    row, hg, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bh, rows, d = q_ref.shape[1], q_ref.shape[2], q_ref.shape[3]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Whole-block skip: every position of block j is causally past every
    # query of this row. State carries; the flush below still runs.
    @pl.when(j * bs <= qmax_ref[row])
    def _update():
        kvp = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rows, bs), 1)
        mask = kvp <= qpos_ref[0]  # (rows, bs): causal + valid_len in one
        kb = k_ref[0].astype(jnp.float32)  # (bs, lanes): bh heads from lane 0
        vb = v_ref[0].astype(jnp.float32)
        if quantized:
            ks_all, vs_all = ks_ref[0], vs_ref[0]  # (bs, Hkv): every head
            col = jax.lax.broadcasted_iota(jnp.int32, ks_all.shape, 1)
        for h in range(bh):  # static: one kv head and its query group
            q = q_ref[0, h].astype(jnp.float32)  # (g*C, D)
            k = kb[:, h * d:(h + 1) * d]  # (bs, D): the head's lane slice
            v = vb[:, h * d:(h + 1) * d]
            if quantized:
                own = col == hg * bh + h  # one column: the sum is exact
                k = k * jnp.sum(jnp.where(own, ks_all, 0.0), axis=1, keepdims=True)
                v = v * jnp.sum(jnp.where(own, vs_all, 0.0), axis=1, keepdims=True)
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)
            s = jnp.where(mask, s / jnp.sqrt(jnp.float32(d)), NEG_INF)
            m_prev, l_prev = m_ref[h], l_ref[h]  # (g*C, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)  # masked lanes underflow to 0
            m_ref[h] = m_new
            l_ref[h] = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
            acc_ref[h] = alpha * acc_ref[h] + jnp.dot(
                p, v, preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(2) - 1)
    def _flush():
        # kv position 0 is always <= q_pos, so l > 0 on every row
        o_ref[0] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


def paged_attn_kernel_call(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    tables: jax.Array,
    q_pos: jax.Array,
    *,
    n_kv_heads: int,
    k_scale: Optional[jax.Array] = None,
    v_scale: Optional[jax.Array] = None,
    block_h: Optional[int] = None,
    interpret: bool = False,
) -> jax.Array:
    """Block-table attention. q: ``(B, C, Hq, D)``; k/v: one lane-dense pool
    layer ``(n_phys_blocks, block_size, lanes)`` holding ``n_kv_heads`` heads
    side by side from lane 0 (int8 when scales are given, scales
    ``(n_phys_blocks, block_size, n_kv_heads)`` f32); tables ``(B, T)``
    int32; q_pos ``(B, C)`` int32 logical positions. Returns ``(B, C, Hq,
    D)`` in q's dtype. ``block_h`` = kv heads per grid step (clamped by
    ``legal_block_h``)."""
    b, c, hq, d = q.shape
    _, bs, width = k.shape
    hkv = n_kv_heads
    assert hkv * d <= width and hq % hkv == 0, (q.shape, k.shape, hkv)
    g = hq // hkv
    quantized = k_scale is not None
    assert (v_scale is not None) == quantized, "k_scale/v_scale come together"
    bh = legal_block_h(hkv, d, block_h or hkv)
    t = tables.shape[1]
    rows = g * c
    q_pos = q_pos.astype(jnp.int32)
    # Query rows grouped under their kv head, row r = (r // C)-th head of
    # the group at chunk token r % C: (B, Hkv, g*C, D). Every kernel block
    # then keeps its last two dims whole, and the GQA grouping is a plain
    # 2-D (g*C, D) x (bs, D) contraction per kv head.
    qg = q.reshape(b, c, hkv, g, d).transpose(0, 2, 3, 1, 4).reshape(b, hkv, rows, d)
    qp_rows = jnp.broadcast_to(q_pos[:, None, :], (b, g, c)).reshape(b, rows, 1)
    q_max = jnp.max(q_pos, axis=1)  # (B,) whole-block skip bound

    def hmap(bb, hh, jj, tbl, qm):  # q/out: row bb, kv-head group hh
        return (bb, hh, 0, 0)

    def pmap(bb, hh, jj, tbl, qm):  # query positions: row bb
        return (bb, 0, 0)

    def kmap(bb, hh, jj, tbl, qm):  # pool: the table names the physical block
        return (tbl[bb, jj], 0, hh)

    def smap(bb, hh, jj, tbl, qm):  # scales: the block's every head
        return (tbl[bb, jj], 0, 0)

    # a head group of whole lane tiles, or every head: the whole padded row
    lanes = width if bh == hkv else bh * d
    in_specs = [
        pl.BlockSpec((1, rows, 1), pmap),
        pl.BlockSpec((1, bh, rows, d), hmap),
        pl.BlockSpec((1, bs, lanes), kmap),
        pl.BlockSpec((1, bs, lanes), kmap),
    ]
    args = [tables.astype(jnp.int32), q_max, qp_rows, qg, k, v]
    if quantized:
        in_specs += [pl.BlockSpec((1, bs, hkv), smap),
                     pl.BlockSpec((1, bs, hkv), smap)]
        args += [k_scale, v_scale]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, hkv // bh, t),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, bh, rows, d), hmap),
        scratch_shapes=[
            pltpu.VMEM((bh, rows, 1), jnp.float32),  # running max
            pltpu.VMEM((bh, rows, 1), jnp.float32),  # running denominator
            pltpu.VMEM((bh, rows, d), jnp.float32),  # output accumulator
        ],
    )
    kernel = functools.partial(_paged_attn_kernel, bs=bs, quantized=quantized)
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, rows, d), q.dtype),
        interpret=interpret,
        name="paged_attention",  # the kernel's name in a profiler trace
    )(*args)
    return out.reshape(b, hkv, g, c, d).transpose(0, 3, 1, 2, 4).reshape(b, c, hq, d)
