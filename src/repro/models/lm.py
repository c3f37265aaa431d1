"""Decoder-only transformer LM family: smollm / qwen / nemotron / phi3 /
grok (MoE) / mixtral (MoE+SWA) / chameleon (early-fusion VLM — VQ image
tokens are ordinary vocabulary entries).

Layers are homogeneous, so parameters are stacked on a leading 'layers' axis
and the forward pass is a single lax.scan — HLO size and compile time are
independent of depth (essential for the 512-device dry-run on 1 CPU core).
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.distributed.constraints import constrain
from repro.nn import embedding
from repro.nn.attention import (
    AttnConfig,
    attn_apply,
    attn_decode_step,
    attn_decode_step_paged,
    attn_init,
    attn_prefill,
    attn_prefill_chunk,
    attn_verify_step,
    init_kv_cache,
)
from repro.nn.mlp import mlp_apply, mlp_init
from repro.nn.moe import MoEConfig, moe_apply, moe_init
from .base import (
    ArchConfig,
    ModelAPI,
    PagedKVLayout,
    init_paged_kv,
    make_norm,
    scan_blocks_aux,
    scan_blocks_with_cache,
    stack_layers,
)

__all__ = ["build_lm"]


def _attn_cfg(cfg: ArchConfig) -> AttnConfig:
    return AttnConfig(
        d_model=cfg.d_model,
        n_heads=cfg.n_heads,
        n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.hd,
        rope_theta=cfg.rope_theta,
        window=cfg.window,
        qkv_bias=cfg.qkv_bias,
        block_q=cfg.block_q,
        tp_pad_heads=cfg.tp_pad_heads,
        paged_route=cfg.paged_attn_route,
    )


def _moe_cfg(cfg: ArchConfig) -> MoEConfig:
    return MoEConfig(
        n_experts=cfg.n_experts,
        top_k=cfg.top_k,
        capacity_factor=cfg.capacity_factor,
        gated=cfg.gated_mlp,
        activation=cfg.activation,
    )


def _layer_init(key: jax.Array, cfg: ArchConfig, phase: str):
    norm_init, _ = make_norm(cfg)
    spec = cfg.linear_spec()
    k1, k2 = jax.random.split(key)
    p = {
        "ln1": norm_init(cfg.d_model),
        "ln2": norm_init(cfg.d_model),
        "attn": attn_init(k1, _attn_cfg(cfg), spec, phase=phase),
    }
    if cfg.n_experts:
        p["moe"] = moe_init(k2, cfg.d_model, cfg.d_ff, _moe_cfg(cfg), spec, phase=phase)
    else:
        p["mlp"] = mlp_init(k2, cfg.d_model, cfg.d_ff, spec, gated=cfg.gated_mlp, phase=phase)
    return p


def _layer_apply(p, x: jax.Array, cfg: ArchConfig, phase: str):
    _, norm_apply = make_norm(cfg)
    spec = cfg.linear_spec()
    x = constrain(x, ("batch", "seq", None))
    x = x + attn_apply(p["attn"], norm_apply(p["ln1"], x), _attn_cfg(cfg), spec, phase=phase)
    h = norm_apply(p["ln2"], x)
    if cfg.n_experts:
        y, aux = moe_apply(p["moe"], h, _moe_cfg(cfg), spec, phase=phase)
    else:
        y = mlp_apply(p["mlp"], h, spec, activation=cfg.activation, phase=phase)
        aux = jnp.zeros((), jnp.float32)
    return x + y, aux


def _layer_ffn(p, x: jax.Array, cfg: ArchConfig, phase: str) -> jax.Array:
    """Residual MLP/MoE half shared by every cached-attention layer step
    (decode, paged decode, chunked and whole-prompt prefill); the MoE aux
    loss is train-only and discarded here."""
    _, norm_apply = make_norm(cfg)
    spec = cfg.linear_spec()
    h = norm_apply(p["ln2"], x)
    if cfg.n_experts:
        y, _aux = moe_apply(p["moe"], h, _moe_cfg(cfg), spec, phase=phase)
    else:
        y = mlp_apply(p["mlp"], h, spec, activation=cfg.activation, phase=phase)
    return x + y


def _layer_decode(p, cache, x, position, cfg: ArchConfig, phase: str):
    _, norm_apply = make_norm(cfg)
    a, new_cache = attn_decode_step(
        p["attn"], norm_apply(p["ln1"], x), cache, position, _attn_cfg(cfg),
        cfg.linear_spec(), phase=phase
    )
    return _layer_ffn(p, x + a, cfg, phase), new_cache


def _layer_decode_paged(p, cache, x, pos_tables, cfg: ArchConfig, phase: str):
    """Per-layer paged decode: ``pos_tables`` bundles the per-row positions
    (S,) and block tables (S, T) that ride through the layer scan together."""
    position, tables = pos_tables
    _, norm_apply = make_norm(cfg)
    a, new_cache = attn_decode_step_paged(
        p["attn"], norm_apply(p["ln1"], x), cache, position, tables, _attn_cfg(cfg),
        cfg.linear_spec(), phase=phase,
    )
    return _layer_ffn(p, x + a, cfg, phase), new_cache


def _layer_verify(p, cache, x, position, cfg: ArchConfig, phase: str):
    """Per-layer multi-token verify (speculative decoding): the dense-slot
    analogue of ``_layer_decode`` over a (B, C) window."""
    _, norm_apply = make_norm(cfg)
    a, new_cache = attn_verify_step(
        p["attn"], norm_apply(p["ln1"], x), cache, position, _attn_cfg(cfg),
        cfg.linear_spec(), phase=phase,
    )
    return _layer_ffn(p, x + a, cfg, phase), new_cache


def _layer_chunk(p, cache, x, start_tables, cfg: ArchConfig, phase: str):
    start, tables = start_tables
    _, norm_apply = make_norm(cfg)
    a, new_cache = attn_prefill_chunk(
        p["attn"], norm_apply(p["ln1"], x), cache, tables, start, _attn_cfg(cfg),
        cfg.linear_spec(), phase=phase,
    )
    return _layer_ffn(p, x + a, cfg, phase), new_cache


def _layer_prefill(p, x, cfg: ArchConfig, phase: str, max_len: int, quantized: bool):
    _, norm_apply = make_norm(cfg)
    a, cache = attn_prefill(
        p["attn"],
        norm_apply(p["ln1"], x),
        _attn_cfg(cfg),
        cfg.linear_spec(),
        max_len=max_len,
        phase=phase,
        quantized=quantized,
        cache_dtype=jnp.dtype(cfg.compute_dtype),
    )
    return _layer_ffn(p, x + a, cfg, phase), cache


def build_lm(cfg: ArchConfig, *, phase: str = "train") -> ModelAPI:
    cdtype = jnp.dtype(cfg.compute_dtype)

    def init(key: jax.Array):
        ke, kl, kn = jax.random.split(key, 3)
        norm_init, _ = make_norm(cfg)
        return {
            "embed": embedding.embed_init(ke, cfg.padded_vocab, cfg.d_model, jnp.dtype(cfg.param_dtype)),
            "layers": stack_layers(kl, cfg.n_layers, lambda k: _layer_init(k, cfg, phase), "layers"),
            "ln_f": norm_init(cfg.d_model),
        }

    def apply_aux(params, batch: Dict[str, Any]):
        tokens = batch["tokens"]  # (B, S)
        x = embedding.embed_apply(params["embed"], tokens, cdtype)
        x, aux = scan_blocks_aux(
            params["layers"], x, lambda p, h: _layer_apply(p, h, cfg, phase), remat=cfg.remat
        )
        _, norm_apply = make_norm(cfg)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), aux / max(cfg.n_layers, 1)

    def apply(params, batch: Dict[str, Any]) -> jax.Array:
        return apply_aux(params, batch)[0]

    def init_cache(batch: int, max_len: int, *, quantized: bool = False, dtype=None):
        dtype = dtype or cdtype
        one = init_kv_cache(batch, _attn_cfg(cfg), max_len, dtype=dtype, quantized=quantized)
        return jax.tree_util.tree_map(
            lambda l: jnp.broadcast_to(l[None], (cfg.n_layers,) + l.shape), one
        )

    def paged_kv_layout(n_phys_blocks: int, block_size: int) -> PagedKVLayout:
        return PagedKVLayout(cfg.n_layers, n_phys_blocks, block_size, cfg.n_kv_heads, cfg.hd)

    def init_paged_cache(n_phys_blocks: int, block_size: int, *, quantized: bool = False,
                         dtype=None):
        """The paged block pool (PagedKVLayout), lane-dense per layer."""
        return init_paged_kv(paged_kv_layout(n_phys_blocks, block_size),
                             dtype=dtype or cdtype, quantized=quantized)

    def decode_step(params, tokens, cache, position):
        """tokens: (B, 1) -> (logits (B, 1, V), new stacked cache)."""
        x = embedding.embed_apply(params["embed"], tokens, cdtype)
        x, new_cache = scan_blocks_with_cache(
            params["layers"],
            cache,
            x,
            lambda p, c, h, pos: _layer_decode(p, c, h, pos, cfg, phase),
            position,
        )
        _, norm_apply = make_norm(cfg)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), new_cache

    def decode_paged(params, tokens, cache, position, tables):
        """Paged one-token decode: cache is the block pool (PagedKVLayout),
        ``tables`` the (S, T) per-slot block tables. Same logits as
        ``decode_step`` over the equivalent dense rows, bit for bit."""
        x = embedding.embed_apply(params["embed"], tokens, cdtype)
        x, new_cache = scan_blocks_with_cache(
            params["layers"],
            cache,
            x,
            lambda p, c, h, pt: _layer_decode_paged(p, c, h, pt, cfg, phase),
            (jnp.asarray(position, jnp.int32), tables),
        )
        _, norm_apply = make_norm(cfg)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), new_cache

    def decode_verify(params, tokens, cache, position):
        """Speculative verify: tokens (B, C) scored at per-row positions
        ``position + [0, C)`` in one step, KV written in place. Returns the
        FULL (B, C, V) logits — slot j's argmax is the greedy successor of
        window token j, exactly what C sequential ``decode_step`` calls
        would have produced (DESIGN.md §10)."""
        x = embedding.embed_apply(params["embed"], tokens, cdtype)
        x, new_cache = scan_blocks_with_cache(
            params["layers"],
            cache,
            x,
            lambda p, c, h, pos: _layer_verify(p, c, h, pos, cfg, phase),
            jnp.asarray(position, jnp.int32),
        )
        _, norm_apply = make_norm(cfg)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), new_cache

    def decode_verify_paged(params, tokens, cache, position, tables):
        """Paged speculative verify: rides ``attn_prefill_chunk``'s batched
        per-row-start block-table append (the chunk path already implements
        the multi-token causal score + OOB scatter-drop), but returns ALL
        (B, C, V) logits instead of selecting one position per row."""
        x = embedding.embed_apply(params["embed"], tokens, cdtype)
        x, new_cache = scan_blocks_with_cache(
            params["layers"],
            cache,
            x,
            lambda p, c, h, st: _layer_chunk(p, c, h, st, cfg, phase),
            (jnp.asarray(position, jnp.int32), tables),
        )
        _, norm_apply = make_norm(cfg)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), new_cache

    def prefill_chunk(params, tokens, cache, tables, start, last_in_chunk):
        """One fixed-size prompt chunk through every layer, appending its KV
        to the block pool. ``last_in_chunk`` ((B,) int32, position *within*
        the chunk) selects which token's logits to return — the last real
        token on the final (right-padded) chunk, ignored on earlier ones."""
        x = embedding.embed_apply(params["embed"], tokens, cdtype)
        x, new_cache = scan_blocks_with_cache(
            params["layers"],
            cache,
            x,
            lambda p, c, h, st: _layer_chunk(p, c, h, st, cfg, phase),
            (jnp.asarray(start, jnp.int32), tables),
        )
        _, norm_apply = make_norm(cfg)
        idx = jnp.asarray(last_in_chunk, jnp.int32).reshape(-1)[:, None, None]
        x = jnp.take_along_axis(x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), new_cache

    def prefill(params, batch, *, max_len: Optional[int] = None, quantized: bool = False,
                last_index=None):
        """Prompt pass: (last-token logits (B,1,V), stacked KV cache).

        ``last_index`` (optional, (B,) int32) selects which position's logits
        to return per row instead of the literal last column — the bucketed
        serving path right-pads prompts to a shape bucket, so the last *real*
        token sits at ``prompt_len - 1``, not at ``-1``. Causality makes the
        selected logits bit-identical to an unpadded prefill.
        """
        tokens = batch["tokens"]
        ml = max_len or tokens.shape[1]
        x = embedding.embed_apply(params["embed"], tokens, cdtype)

        def step(carry, p):
            y, cache = _layer_prefill(p, carry, cfg, phase, ml, quantized)
            return y, cache

        x, caches = jax.lax.scan(step, x, params["layers"])
        _, norm_apply = make_norm(cfg)
        if last_index is None:
            x = x[:, -1:]
        else:
            idx = jnp.asarray(last_index, jnp.int32).reshape(-1)[:, None, None]
            x = jnp.take_along_axis(x, jnp.broadcast_to(idx, (x.shape[0], 1, x.shape[2])), axis=1)
        x = norm_apply(params["ln_f"], x)
        return embedding.unembed_apply(params["embed"], x), caches

    return ModelAPI(
        init=init,
        apply=apply,
        init_cache=init_cache,
        decode_step=decode_step,
        prefill=prefill,
        apply_aux=apply_aux,
        paged_kv_layout=paged_kv_layout,
        init_paged_cache=init_paged_cache,
        decode_paged=decode_paged,
        prefill_chunk=prefill_chunk,
        decode_verify=decode_verify,
        decode_verify_paged=decode_verify_paged,
    )
