"""Grouped-query attention with blockwise (flash-style) softmax, sliding
windows, KV caches (full + SWA ring buffer) and cross-attention.

Projections route through the backend-switchable linear layer, so attention
runs in dense / bika / bnn / qnn8 mode uniformly. Score math and softmax stay
fp32 (DESIGN.md §6).

Blockwise path: scan over query blocks; each block sees the full KV but the
(block_q x S_kv) score tile is the only large intermediate, and the scan body
is rematerialized (jax.checkpoint) so the backward pass recomputes scores
instead of storing them — the XLA analogue of flash attention.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.constraints import constrain
from repro.kernels import ops as kernel_ops
from .linear import LinearSpec, linear_apply, linear_init
from .rotary import apply_rope

__all__ = [
    "AttnConfig",
    "attn_init",
    "attn_apply",
    "attn_prefill",
    "attn_decode_step",
    "attn_decode_step_paged",
    "attn_prefill_chunk",
    "attn_verify_step",
    "init_kv_cache",
    "dot_attention",
    "blockwise_attention",
    "paged_gather",
]

NEG_INF = -1e30


@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    rope_theta: float = 10000.0
    window: Optional[int] = None  # sliding-window size (Mixtral SWA)
    qkv_bias: bool = False  # Qwen-style
    causal: bool = True
    block_q: int = 256  # blockwise attention query tile
    cross: bool = False  # cross-attention (KV from encoder output)
    # pad the head dim to a multiple of the mesh 'model' axis so attention
    # tensor-parallelizes when n_heads doesn't divide it (smollm: 15 heads vs
    # model=16 otherwise replicates the whole attention — §Perf hillclimb).
    tp_pad_heads: bool = False
    # paged serving attention route: "fused" walks the block table through
    # the Pallas online-softmax kernel (kernels/paged_attn.py, the default);
    # "gather" keeps the XLA paged_gather + dot_attention path, which is the
    # bit-parity oracle against the dense per-row decode.
    paged_route: str = "fused"


def _tp_size() -> int:
    from repro.distributed.constraints import _context_mesh

    mesh = _context_mesh()
    return int(mesh.shape.get("model", 1)) if mesh is not None else 1


def _maybe_pad_heads(q: jax.Array, k: jax.Array, v: jax.Array, cfg: AttnConfig):
    """If heads don't divide the TP axis: expand GQA->MHA and zero-pad heads
    to the next multiple. Returns (q, k, v, orig_hq); padded heads attend to
    zero keys (uniform softmax) and are sliced away by the caller."""
    tp = _tp_size()
    hq, hkv = q.shape[2], k.shape[2]
    if not cfg.tp_pad_heads or tp == 1 or (hq % tp == 0 and hkv % tp == 0):
        return q, k, v, hq
    g = hq // hkv
    if g > 1:  # expand kv to one head per q head
        k = jnp.repeat(k, g, axis=2)
        v = jnp.repeat(v, g, axis=2)
    pad = (-hq) % tp
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        q, k, v = jnp.pad(q, widths), jnp.pad(k, widths), jnp.pad(v, widths)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "heads", None))
    v = constrain(v, ("batch", "seq", "heads", None))
    return q, k, v, hq


def attn_init(key: jax.Array, cfg: AttnConfig, spec: LinearSpec, *, phase: str = "train"):
    kq, kk, kv, ko = jax.random.split(key, 4)
    qspec = dataclasses.replace(spec, bias=cfg.qkv_bias)
    d, hd = cfg.d_model, cfg.head_dim
    return {
        "wq": linear_init(kq, d, cfg.n_heads * hd, qspec, axes=("embed", "heads"), phase=phase),
        "wk": linear_init(kk, d, cfg.n_kv_heads * hd, qspec, axes=("embed", "kv_heads"), phase=phase),
        "wv": linear_init(kv, d, cfg.n_kv_heads * hd, qspec, axes=("embed", "kv_heads"), phase=phase),
        "wo": linear_init(ko, cfg.n_heads * hd, d, spec, axes=("heads", "embed"), phase=phase),
    }


def _split_heads(x: jax.Array, n: int, hd: int) -> jax.Array:
    return x.reshape(x.shape[:-1] + (n, hd))


def _gqa_scores(q: jax.Array, k: jax.Array) -> jax.Array:
    """q: (B,Sq,Hkv,G,D), k: (B,Skv,Hkv,D) -> (B,Hkv,G,Sq,Skv) fp32."""
    return jnp.einsum("bshgd,bthd->bhgst", q, k, preferred_element_type=jnp.float32)


def _gqa_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p: (B,Hkv,G,Sq,Skv), v: (B,Skv,Hkv,D) -> (B,Sq,Hkv,G,D)."""
    return jnp.einsum("bhgst,bthd->bshgd", p, v.astype(p.dtype))


def dot_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    q_positions: jax.Array,
    kv_positions: jax.Array,
    causal: bool = True,
    window: Optional[int] = None,
    kv_valid_len: Optional[jax.Array] = None,
) -> jax.Array:
    """Unblocked GQA attention. q: (B,Sq,Hq,D); k,v: (B,Skv,Hkv,D).

    ``q_positions`` / ``kv_positions`` are (Sq,) / (Skv,) when positions are
    shared across the batch, or (B,Sq) / (B,Skv) for per-row positions (the
    continuous-batching decode path, where every slot sits at its own step).
    """
    b, sq, hq, d = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    scores = _gqa_scores(qg, k) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    qp = q_positions if q_positions.ndim == 2 else q_positions[None]  # (1|B, Sq)
    kp = kv_positions if kv_positions.ndim == 2 else kv_positions[None]  # (1|B, Skv)
    mask = kp[:, None, :] >= 0  # ring slots not yet written recover negative positions
    if causal:
        mask = mask & (kp[:, None, :] <= qp[:, :, None])
    if window is not None:
        mask = mask & (kp[:, None, :] > qp[:, :, None] - window)
    scores = jnp.where(mask[:, None, None], scores, NEG_INF)
    if kv_valid_len is not None:
        valid = kp < kv_valid_len[:, None]  # (B, Skv)
        scores = jnp.where(valid[:, None, None, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = _gqa_out(p, v)
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def blockwise_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 256,
) -> jax.Array:
    """Self-attention over aligned positions 0..S-1 with bounded memory.

    Scans over query tiles; the scan body is rematerialized so backward
    recomputes the (block_q x S) score tile instead of saving all of them.
    """
    b, s, hq, d = q.shape
    if s <= block_q:
        pos = jnp.arange(s)
        return dot_attention(
            q, k, v, q_positions=pos, kv_positions=pos, causal=causal, window=window
        )
    assert s % block_q == 0, (s, block_q)
    nblk = s // block_q
    kv_pos = jnp.arange(s)
    qb = jnp.moveaxis(q.reshape(b, nblk, block_q, hq, d), 1, 0)  # (nblk, B, bq, H, D)

    @jax.checkpoint
    def body(carry, args):
        i, qblk = args
        qpos = i * block_q + jnp.arange(block_q)
        out = dot_attention(
            qblk, k, v, q_positions=qpos, kv_positions=kv_pos, causal=causal, window=window
        )
        return carry, out

    _, outs = jax.lax.scan(body, 0, (jnp.arange(nblk), qb))
    return jnp.moveaxis(outs, 0, 1).reshape(b, s, hq, d)


def attn_apply(
    params,
    x: jax.Array,
    cfg: AttnConfig,
    spec: LinearSpec,
    *,
    phase: str = "train",
    kv_x: Optional[jax.Array] = None,
) -> jax.Array:
    """Full-sequence (train / prefill) attention. x: (B, S, D)."""
    b, s, _ = x.shape
    hd = cfg.head_dim
    src = x if kv_x is None else kv_x
    q = _split_heads(linear_apply(params["wq"], x, spec, phase=phase), cfg.n_heads, hd)
    k = _split_heads(linear_apply(params["wk"], src, spec, phase=phase), cfg.n_kv_heads, hd)
    v = _split_heads(linear_apply(params["wv"], src, spec, phase=phase), cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    if not cfg.cross:
        pos = jnp.arange(s)
        q = apply_rope(q, pos, cfg.rope_theta)
        k = apply_rope(k, jnp.arange(k.shape[1]), cfg.rope_theta)
        q, k, v, hq_orig = _maybe_pad_heads(q, k, v, cfg)
        out = blockwise_attention(
            q, k, v, causal=cfg.causal, window=cfg.window, block_q=cfg.block_q
        )
        out = out[:, :, :hq_orig]
    else:
        skv = k.shape[1]
        out = dot_attention(
            q,
            k,
            v,
            q_positions=jnp.arange(s),
            kv_positions=jnp.arange(skv),
            causal=False,
        )
    return linear_apply(params["wo"], out.reshape(b, s, -1), spec, phase=phase)


def attn_prefill(
    params,
    x: jax.Array,
    cfg: AttnConfig,
    spec: LinearSpec,
    *,
    max_len: int,
    phase: str = "serve",
    quantized: bool = False,
    cache_dtype=jnp.bfloat16,
):
    """Full-prompt attention that also emits the KV cache for decode.

    Returns (y, cache). Cache layout matches init_kv_cache/attn_decode_step:
    full cache of length ``max_len`` written at slots [0, S) — or, with SWA,
    a ring of length L = min(window, max_len) holding the last L positions
    (requires S % L == 0 or S <= L so ring slots line up with positions).
    """
    b, s, _ = x.shape
    hd = cfg.head_dim
    q = _split_heads(linear_apply(params["wq"], x, spec, phase=phase), cfg.n_heads, hd)
    k = _split_heads(linear_apply(params["wk"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    v = _split_heads(linear_apply(params["wv"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    pos = jnp.arange(s)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)  # cache stores post-RoPE keys
    out = blockwise_attention(q, k, v, causal=cfg.causal, window=cfg.window, block_q=cfg.block_q)
    y = linear_apply(params["wo"], out.reshape(b, s, -1), spec, phase=phase)

    length = min(max_len, cfg.window) if cfg.window is not None else max_len
    if cfg.window is not None and s > length:
        assert s % length == 0, (s, length)
        kc, vc = k[:, -length:], v[:, -length:]
    elif s < length:
        padw = ((0, 0), (0, length - s), (0, 0), (0, 0))
        kc, vc = jnp.pad(k, padw), jnp.pad(v, padw)
    else:
        kc, vc = k[:, -length:], v[:, -length:]
    if quantized:
        kq, ks = _quantize_kv(kc)
        vq, vs = _quantize_kv(vc)
        cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
    else:
        cache = {"k": kc.astype(cache_dtype), "v": vc.astype(cache_dtype)}
    return y, cache


# ---------------------------------------------------------------------------
# KV caches (full + SWA ring) and single-token decode
# ---------------------------------------------------------------------------


def init_kv_cache(
    batch: int, cfg: AttnConfig, max_len: int, dtype=jnp.bfloat16, quantized: bool = False
):
    """Cache pytree. With ``quantized`` keys/values are int8 + per-(pos,head)
    scales (the int8-KV optimization; see EXPERIMENTS.md §Perf)."""
    length = min(max_len, cfg.window) if cfg.window is not None else max_len
    shape = (batch, length, cfg.n_kv_heads, cfg.head_dim)
    if quantized:
        return {
            "k": jnp.zeros(shape, jnp.int8),
            "v": jnp.zeros(shape, jnp.int8),
            "k_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
            "v_scale": jnp.zeros(shape[:-1] + (1,), jnp.float32),
        }
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _quantize_kv(x: jax.Array):
    scale = jnp.max(jnp.abs(x), axis=-1, keepdims=True).astype(jnp.float32) / 127.0
    scale = jnp.maximum(scale, 1e-8)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q: jax.Array, scale: jax.Array, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def attn_decode_step(
    params,
    x: jax.Array,
    cache,
    position: jax.Array,
    cfg: AttnConfig,
    spec: LinearSpec,
    *,
    phase: str = "serve",
):
    """One-token decode. x: (B, 1, D); position: scalar int32 (same for the
    whole batch) or (B,) int32 (per-row positions — the continuous-batching
    slot path, where each batch row is an independent request).

    Full cache: write at index ``position``.  SWA: ring buffer of size
    ``window`` written at ``position % window``; positions are recovered from
    slot indices for the RoPE-consistent mask (keys are stored post-RoPE).
    """
    b = x.shape[0]
    hd = cfg.head_dim
    q = _split_heads(linear_apply(params["wq"], x, spec, phase=phase), cfg.n_heads, hd)
    k = _split_heads(linear_apply(params["wk"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    v = _split_heads(linear_apply(params["wv"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    position = jnp.asarray(position, jnp.int32)
    per_row = position.ndim == 1
    cache_len = cache["k"].shape[1]
    quantized = "k_scale" in cache

    if per_row:
        pos = position[:, None]  # (B, 1): per-row RoPE / mask positions
    else:
        pos = jnp.full((1,), position, jnp.int32)
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    slot = position % cache_len if cfg.window is not None else position

    if per_row:
        rows = jnp.arange(b)

        def write(buf, upd):  # upd: (B, 1, H, D|1) scattered at per-row slots
            return buf.at[rows, slot].set(upd[:, 0].astype(buf.dtype))

    else:

        def write(buf, upd):
            return jax.lax.dynamic_update_slice(buf, upd.astype(buf.dtype), (0, slot, 0, 0))

    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {
            "k": write(cache["k"], kq),
            "v": write(cache["v"], vq),
            "k_scale": write(cache["k_scale"], ks),
            "v_scale": write(cache["v_scale"], vs),
        }
        k_all = _dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_all = _dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        new_cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
        k_all = new_cache["k"].astype(x.dtype)
        v_all = new_cache["v"].astype(x.dtype)

    slots = jnp.arange(cache_len)
    if cfg.window is not None:
        # slot s holds token position p - ((p - s) mod L), the most recent
        # position congruent to s (ring buffer; L == min(window, max_len)).
        # Per-row positions broadcast to a (B, L) position map.
        kv_positions = (
            position[:, None] - jnp.mod(position[:, None] - slots[None], cache_len)
            if per_row
            else position - jnp.mod(position - slots, cache_len)
        )
        # unwritten slots recover negative positions and are masked in dot_attention
        valid_len = None
    else:
        kv_positions = slots
        valid_len = (
            position + 1 if per_row else jnp.full((b,), position + 1, jnp.int32)
        )

    out = dot_attention(
        q,
        k_all,
        v_all,
        q_positions=pos,
        kv_positions=kv_positions,
        causal=True,
        window=cfg.window,
        kv_valid_len=valid_len,
    )
    y = linear_apply(params["wo"], out.reshape(b, 1, -1), spec, phase=phase)
    return y, new_cache


def attn_verify_step(
    params,
    x: jax.Array,
    cache,
    position: jax.Array,
    cfg: AttnConfig,
    spec: LinearSpec,
    *,
    phase: str = "serve",
):
    """Multi-token verify over a dense per-slot cache (speculative decoding).

    x: (B, C, D) — each row's verify window, occupying logical positions
    ``position[i] + [0, C)``. The window's K/V are scattered at those
    positions (out-of-range positions — a window overhanging ``max_len``
    near the end of a row's budget — are dropped, never clamped), then the
    window's queries attend the whole cache row under the causal
    ``kv_pos <= q_pos`` mask. Because every speculative round writes C
    *consecutive* positions and advances by 1..C, any stale keys a rejected
    window left behind sit inside the next round's write range or causally
    in the future of every query — so no ``kv_valid_len`` operand is needed
    and the returned (B, C, V)-shaped logits are exactly what C sequential
    ``attn_decode_step`` calls over the same tokens would produce
    (DESIGN.md §10). C == 1 IS the decode step, same math, wider signature.
    SWA is unsupported: a rejected window cannot be rolled back out of a
    ring cache that already evicted the overwritten positions.
    """
    assert cfg.window is None, "speculative verify does not support sliding windows"
    b, c, _ = x.shape
    hd = cfg.head_dim
    q = _split_heads(linear_apply(params["wq"], x, spec, phase=phase), cfg.n_heads, hd)
    k = _split_heads(linear_apply(params["wk"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    v = _split_heads(linear_apply(params["wv"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    position = jnp.asarray(position, jnp.int32)
    lp = position[:, None] + jnp.arange(c, dtype=jnp.int32)  # (B, C)
    q = apply_rope(q, lp, cfg.rope_theta)
    k = apply_rope(k, lp, cfg.rope_theta)

    cache_len = cache["k"].shape[1]
    rows = jnp.broadcast_to(jnp.arange(b)[:, None], (b, c))
    # overhang positions (>= max_len) scatter-drop instead of clamping onto
    # a live slot — the same discipline as attn_prefill_chunk's OOB blocks
    quantized = "k_scale" in cache

    def write(buf, upd):  # upd: (B, C, H, D|1) scattered at (row, lp) pairs
        return buf.at[rows, lp].set(upd.astype(buf.dtype), mode="drop")

    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {
            "k": write(cache["k"], kq),
            "v": write(cache["v"], vq),
            "k_scale": write(cache["k_scale"], ks),
            "v_scale": write(cache["v_scale"], vs),
        }
        k_all = _dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        v_all = _dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        new_cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}
        k_all = new_cache["k"].astype(x.dtype)
        v_all = new_cache["v"].astype(x.dtype)

    out = dot_attention(
        q,
        k_all,
        v_all,
        q_positions=lp,
        kv_positions=jnp.arange(cache_len),
        causal=True,
    )
    y = linear_apply(params["wo"], out.reshape(b, c, -1), spec, phase=phase)
    return y, new_cache


# ---------------------------------------------------------------------------
# Paged KV: block-pool cache, gather-based decode, chunked prefill
# ---------------------------------------------------------------------------


def paged_gather(leaf: jax.Array, table: jax.Array) -> jax.Array:
    """Assemble per-row logical KV views from a block pool.

    leaf: one layer of the lane-dense pool (models/base.PagedKVLayout),
    ``(n_phys_blocks, block_size, lanes)``: payload rows of every kv head's
    ``head_dim`` values, padded to whole lane tiles, or ``n_kv_heads``
    int8 scales; table: ``(B, T)`` int32 block ids. Returns ``(B, T *
    block_size, lanes)`` where row ``i``'s position ``p`` is
    ``leaf[table[i, p // bs], p % bs]`` — exactly the dense slot row the
    block writes were scattered from, so attention over the gathered view
    (``_unfold_heads``) is bit-identical to the dense path.
    """
    g = leaf[table]  # (B, T, bs, lanes)
    return g.reshape(g.shape[0], g.shape[1] * g.shape[2], *g.shape[3:])


def _fold_heads(x: jax.Array, lanes: int) -> jax.Array:
    """(..., H, D|1) -> (..., lanes): a pool row, heads side by side and
    zero-padded to the leaf's ``lanes`` (PagedKVLayout.row_lanes)."""
    row = x.reshape(x.shape[:-2] + (-1,))
    return jnp.pad(row, [(0, 0)] * (row.ndim - 1) + [(0, lanes - row.shape[-1])])


def _unfold_heads(x: jax.Array, n_kv_heads: int, head_dim: int) -> jax.Array:
    """(..., lanes) -> (..., H, D): the heads of padded pool rows."""
    return x[..., :n_kv_heads * head_dim].reshape(x.shape[:-1] + (n_kv_heads, head_dim))


def _paged_gather_kv(cache, table: jax.Array, cfg: AttnConfig, dtype):
    """The gather route's per-row ``(B, max_len, H, D)`` k and v views of
    one pool layer, dequantized when the pool is int8."""

    def view(name):
        width = 1 if name.endswith("_scale") else cfg.head_dim
        return _unfold_heads(paged_gather(cache[name], table), cfg.n_kv_heads, width)

    if "k_scale" in cache:
        return (_dequantize_kv(view("k"), view("k_scale"), dtype),
                _dequantize_kv(view("v"), view("v_scale"), dtype))
    return view("k").astype(dtype), view("v").astype(dtype)


def attn_decode_step_paged(
    params,
    x: jax.Array,
    cache,
    position: jax.Array,
    table: jax.Array,
    cfg: AttnConfig,
    spec: LinearSpec,
    *,
    phase: str = "serve",
):
    """One-token decode against a paged block pool (this layer's
    PagedKVLayout leaves: ``(n_phys_blocks, block_size, row_lanes)``
    payloads, ``(n_phys_blocks, block_size, H)`` int8 scales).

    ``position``: (B,) per-row next-write positions; ``table``: (B, T) block
    tables (T = max_len // block_size). The new K/V is scattered as one
    lane-dense row at physical ``(table[i, p // bs], p % bs)``; then
    attention routes per ``cfg.paged_route``: the default ``"fused"`` walks
    the block table through the Pallas online-softmax kernel (one pass over
    the pool, read in place, no gathered copy, fused int8 dequant —
    token-for-token the gather route's outputs within float rounding),
    while ``"gather"`` assembles per-row ``(B, max_len, ...)`` views — the
    same bytes, positions and masks as the dense per-row
    ``attn_decode_step``, so gather outputs are bit-identical to dense.
    Inactive rows must point their whole table at the reserved parking
    block (their junk writes race only with each other). SWA is
    unsupported: a ring cache has no block-aligned logical order to page.
    """
    assert cfg.window is None, "paged decode does not support sliding-window caches"
    b = x.shape[0]
    hd = cfg.head_dim
    q = _split_heads(linear_apply(params["wq"], x, spec, phase=phase), cfg.n_heads, hd)
    k = _split_heads(linear_apply(params["wk"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    v = _split_heads(linear_apply(params["wv"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    position = jnp.asarray(position, jnp.int32)
    pos = position[:, None]  # (B, 1): per-row RoPE / mask positions
    q = apply_rope(q, pos, cfg.rope_theta)
    k = apply_rope(k, pos, cfg.rope_theta)

    bs = cache["k"].shape[1]
    blk = jnp.take_along_axis(table, (position // bs)[:, None], axis=1)[:, 0]  # (B,)
    off = position % bs
    quantized = "k_scale" in cache

    def write(buf, upd):  # upd: (B, 1, H, D|1) -> one pool row at (blk, off)
        return buf.at[blk, off].set(_fold_heads(upd[:, 0], buf.shape[-1]).astype(buf.dtype))

    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {
            "k": write(cache["k"], kq),
            "v": write(cache["v"], vq),
            "k_scale": write(cache["k_scale"], ks),
            "v_scale": write(cache["v_scale"], vs),
        }
    else:
        new_cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}

    if cfg.paged_route == "fused":
        out = kernel_ops.paged_attention(
            q, new_cache["k"], new_cache["v"], table, pos, n_kv_heads=cfg.n_kv_heads,
            k_scale=new_cache.get("k_scale"), v_scale=new_cache.get("v_scale"),
        )
    else:
        k_all, v_all = _paged_gather_kv(new_cache, table, cfg, x.dtype)
        max_len = table.shape[1] * bs
        out = dot_attention(
            q,
            k_all,
            v_all,
            q_positions=pos,
            kv_positions=jnp.arange(max_len),
            causal=True,
            kv_valid_len=position + 1,
        )
    y = linear_apply(params["wo"], out.reshape(b, 1, -1), spec, phase=phase)
    return y, new_cache


def attn_prefill_chunk(
    params,
    x: jax.Array,
    cache,
    table: jax.Array,
    start: jax.Array,
    cfg: AttnConfig,
    spec: LinearSpec,
    *,
    phase: str = "serve",
):
    """One fixed-size prompt chunk appended to a paged block pool.

    x: (B, C, D) embedded chunk occupying logical positions
    ``start + [0, C)`` of each row; K/V are scattered into the pool via the
    block table, then the chunk's queries attend the gathered
    ``(B, max_len, ...)`` view causally — so chunk ``n`` sees every earlier
    chunk's (and any shared prefix's) cached keys. Positions past
    ``max_len`` (final-chunk right-padding overhang) are dropped by an
    explicit OOB scatter, never clamped onto live rows. Pad positions
    inside ``max_len`` write junk that stays causally in the future of
    every real query and is overwritten by decode before it is attended —
    the same argument as the bucketed right-pad (DESIGN.md §4.2).
    """
    assert cfg.window is None, "paged prefill does not support sliding-window caches"
    b, c, _ = x.shape
    hd = cfg.head_dim
    q = _split_heads(linear_apply(params["wq"], x, spec, phase=phase), cfg.n_heads, hd)
    k = _split_heads(linear_apply(params["wk"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    v = _split_heads(linear_apply(params["wv"], x, spec, phase=phase), cfg.n_kv_heads, hd)
    q = constrain(q, ("batch", "seq", "heads", None))
    k = constrain(k, ("batch", "seq", "kv_heads", None))
    v = constrain(v, ("batch", "seq", "kv_heads", None))
    start = jnp.asarray(start, jnp.int32)
    lp = start[:, None] + jnp.arange(c, dtype=jnp.int32)  # (B, C) logical positions
    q = apply_rope(q, lp, cfg.rope_theta)
    k = apply_rope(k, lp, cfg.rope_theta)

    n_phys, bs = cache["k"].shape[:2]
    max_len = table.shape[1] * bs
    idx = jnp.clip(lp // bs, 0, table.shape[1] - 1)
    blk = jnp.take_along_axis(table, idx, axis=1)  # (B, C)
    # overhang positions (>= max_len) get an out-of-range block id: the
    # scatter drops them instead of clamping onto a live block
    blk = jnp.where(lp < max_len, blk, n_phys)
    off = lp % bs
    quantized = "k_scale" in cache

    def write(buf, upd):  # upd: (B, C, H, D|1) -> pool rows at (blk, off) pairs
        return buf.at[blk, off].set(_fold_heads(upd, buf.shape[-1]).astype(buf.dtype),
                                    mode="drop")

    if quantized:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        new_cache = {
            "k": write(cache["k"], kq),
            "v": write(cache["v"], vq),
            "k_scale": write(cache["k_scale"], ks),
            "v_scale": write(cache["v_scale"], vs),
        }
    else:
        new_cache = {"k": write(cache["k"], k), "v": write(cache["v"], v)}

    if cfg.paged_route == "fused":
        # the chunk's own keys were just scattered, so the block walk sees
        # them; intra-chunk causality is the same kv_pos <= q_pos mask
        out = kernel_ops.paged_attention(
            q, new_cache["k"], new_cache["v"], table, lp, n_kv_heads=cfg.n_kv_heads,
            k_scale=new_cache.get("k_scale"), v_scale=new_cache.get("v_scale"),
        )
    else:
        k_all, v_all = _paged_gather_kv(new_cache, table, cfg, x.dtype)
        out = dot_attention(
            q,
            k_all,
            v_all,
            q_positions=lp,
            kv_positions=jnp.arange(max_len),
            causal=True,
        )
    y = linear_apply(params["wo"], out.reshape(b, c, -1), spec, phase=phase)
    return y, new_cache
