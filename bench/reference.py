"""Plain reference of a bika llama-style LM, for the correctness check.

Written from the published description and the configuration file alone:
it imports nothing of the program. Weights come from ``bench/model.py``'s
rule (the benchmark's, from the seed), made one layer at a time inside the
scan, so the reference never holds more than one layer of edges.

One layer, on a row of ``T`` tokens (``x`` the residual stream)::

    h = rmsnorm(x)                      # eps from the file, scale 1
    q, k, v = P_q(h), P_k(h), P_v(h)    # bika projections, below
    q, k = rope(q), rope(k)             # rotate-half, theta from the file
    x = x + P_o(causal_gqa_softmax_attention(q, k, v))
    h = rmsnorm(x)
    x = x + P_down(silu(P_gate(h)) * P_up(h))

and the logits are ``rmsnorm(x) @ E^T`` with the tied embedding ``E``.
A bika projection with thresholds ``tau`` and signs ``s`` (K, N), per the
BiKA paper's comparator-accumulate form with m = 1::

    a = clip(round(h / act_scale), -128, 127)
    P(h)[n] = sum_k s[k, n] * (+1 if a[k] >= tau[k, n] else -1) / sqrt(K)

Several requests share a row: ``segments`` gives each token its request
(attention stays inside it) and ``positions`` its position in the request.
Everything is float32, and every matmul runs at "highest" precision.
"""
from __future__ import annotations

import functools
import json

import jax
import jax.numpy as jnp

from bench import model

NEG = -1e30


def rmsnorm(x, eps):
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return y.astype(x.dtype)


def bika(h, tau, s, act_scale, block=16):
    """Comparator-accumulate over K, ``block`` rows at a time."""
    a = jnp.clip(jnp.round(h.astype(jnp.float32) / act_scale), -128, 127)
    t, k = a.shape
    tau = tau.astype(jnp.float32)
    s = s.astype(jnp.float32)

    def rows(ab):
        return jnp.sum(jnp.where(ab[:, :, None] >= tau[None], s[None], -s[None]), axis=1)

    y = jax.lax.map(rows, a.reshape(t // block, block, k)).reshape(t, -1)
    return (y / jnp.sqrt(jnp.float32(k))).astype(h.dtype)


def rope(x, positions, theta):
    """Rotate-half rotary embedding. x: (T, H, D)."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = positions[:, None].astype(jnp.float32) * inv  # (T, D/2)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., : d // 2], xf[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1).astype(x.dtype)


def attention(q, k, v, positions, segments):
    """Causal softmax attention inside each segment; GQA by repeating each
    kv head over its group of query heads. q: (T, Hq, D), k/v: (T, Hkv, D)."""
    t, hq, d = q.shape
    g = hq // k.shape[1]
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32), k.astype(jnp.float32))
    s = s / jnp.sqrt(jnp.float32(d))
    ok = (segments[:, None] == segments[None, :]) & (positions[None, :] <= positions[:, None])
    p = jax.nn.softmax(jnp.where(ok[None], s, NEG), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v.astype(jnp.float32)).astype(q.dtype)


@functools.partial(jax.jit, static_argnames=("cfg_json",))
def _logits(key, tokens, positions, segments, cfg_json):
    cfg = json.loads(cfg_json)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    act = float(cfg["serve"]["act_scale"])
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = model.head_dim(cfg)
    t = tokens.shape[0]
    with jax.default_matmul_precision("highest"):
        emb = model.embedding(key, cfg)
        x = emb[tokens]

        def layer(x, l):
            e = model.layer_edges(key, cfg, l)
            proj = lambda name, h: bika(h, *e[name], act)
            h = rmsnorm(x, eps)
            q = rope(proj("attn/wq", h).reshape(t, hq, hd), positions, theta)
            k = rope(proj("attn/wk", h).reshape(t, hkv, hd), positions, theta)
            v = proj("attn/wv", h).reshape(t, hkv, hd)
            x = x + proj("attn/wo", attention(q, k, v, positions, segments).reshape(t, hq * hd))
            h = rmsnorm(x, eps)
            y = jax.nn.silu(proj("mlp/gate", h)) * proj("mlp/up", h)
            return x + proj("mlp/down", y), None

        x, _ = jax.lax.scan(layer, x, jnp.arange(cfg["num_hidden_layers"], dtype=jnp.int32))
        return jnp.einsum("td,vd->tv", rmsnorm(x, eps), emb)


def logits(cfg: dict, seed: int, tokens, positions, segments):
    """Reference logits (T, padded vocab) of one packed row; T must be a
    multiple of 16."""
    keep = {k: cfg[k] for k in ("hidden_size", "intermediate_size", "num_attention_heads",
                                "num_key_value_heads", "num_hidden_layers", "rms_norm_eps",
                                "rope_theta", "vocab_size")}
    keep["head_dim"] = model.head_dim(cfg)
    keep["serve"] = {"act_scale": cfg["serve"]["act_scale"]}
    return _logits(model.seed_key(seed), jnp.asarray(tokens, jnp.int32),
                   jnp.asarray(positions, jnp.int32), jnp.asarray(segments, jnp.int32),
                   json.dumps(keep, sort_keys=True))
