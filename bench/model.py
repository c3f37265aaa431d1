"""A configuration file -> the program's serve model, engine and weights.

The configuration files (``bench/configs/<name>.json``) hold the published
``config.json`` keys as they are run, plus a ``serve`` group with the
engine's settings. This module maps them onto the program's ``ArchConfig``
and ``ServeEngine``, and makes serve-form weights on the device from the
seed in one jitted call. The weight rule lives here, keyed by the name of
each parameter, so the plain reference (``bench/reference.py``) can make the
same weights one layer at a time without reading anything the program made.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

EMBED_STD = 0.02


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from any non-negative whole number (the driver's seeds go
    past 32 bits)."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def name_key(key: jax.Array, name: str) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)


def head_dim(cfg: dict) -> int:
    return cfg.get("head_dim") or cfg["hidden_size"] // cfg["num_attention_heads"]


def padded_vocab(cfg: dict) -> int:
    return -(-cfg["vocab_size"] // 256) * 256


def bika_edges(key: jax.Array, k: int, n: int, act_scale: float):
    """One projection's serve form: int8 thresholds and int8 signs (K, N),
    as the train -> serve conversion would make them from a freshly
    initialised (w, beta)."""
    kw, kb = jax.random.split(key)
    bound = 1.0 / k**0.5
    w = jax.random.uniform(kw, (k, n), jnp.float32, -bound, bound)
    beta = jax.random.uniform(kb, (k, n), jnp.float32, -bound, bound)
    safe = jnp.where(w == 0, 1.0, w)
    tau = jnp.clip(jnp.round(-beta / safe / act_scale), -128, 127).astype(jnp.int8)
    s = jnp.where(w > 0, 1, -1).astype(jnp.int8)
    return tau, s


def projection_shapes(cfg: dict) -> dict:
    """(K, N) of each projection of one layer, by the program's names."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return {"attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d), "mlp/gate": (d, f), "mlp/up": (d, f),
            "mlp/down": (f, d)}


def layer_edges(key: jax.Array, cfg: dict, layer) -> dict:
    """Every projection of layer ``layer``: {name: (tau, s)}."""
    scale = cfg["serve"]["act_scale"]
    return {name: bika_edges(jax.random.fold_in(name_key(key, name), layer), k, n, scale)
            for name, (k, n) in projection_shapes(cfg).items()}


def embedding(key: jax.Array, cfg: dict) -> jax.Array:
    shape = (padded_vocab(cfg), cfg["hidden_size"])
    return jax.random.normal(name_key(key, "embed"), shape, jnp.float32) * EMBED_STD


def arch_config(cfg: dict):
    """The program's ArchConfig for a configuration file."""
    from repro.models.base import ArchConfig

    sv = cfg["serve"]
    if cfg.get("hidden_act") != "silu" or cfg.get("attention_bias"):
        raise ValueError(f"{cfg['name']}: only silu, bias-free llama-style blocks map "
                         "onto the repo's lm family")
    if sv["bika_m"] != 1 or sv["compute_mode"] != "bika" or not cfg.get("tie_word_embeddings"):
        raise ValueError(f"{cfg['name']}: the weight rule covers tied-embedding bika m=1 only")
    return ArchConfig(
        name=cfg["name"], family="lm", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], head_dim=head_dim(cfg), activation="silu",
        gated_mlp=True, norm="rmsnorm", rope_theta=float(cfg["rope_theta"]),
        compute_mode=sv["compute_mode"], bika_m=sv["bika_m"], bika_impl=sv["bika_impl"],
        pack_signs=sv["pack_signs"], param_dtype=sv["param_dtype"],
        compute_dtype=sv["compute_dtype"], paged_attn_route=sv["paged_attn_route"],
        remat=False,
    )


def make_params(cfg: dict, api, seed: int):
    """Serve-form weights on the device, in one jitted call from the seed,
    in the program's parameter tree (its structure read off ``api.init``)."""
    from repro.nn.module import unbox

    n_layers = cfg["num_hidden_layers"]
    template = jax.eval_shape(lambda: unbox(api.init(jax.random.PRNGKey(0))))
    paths = [tuple(getattr(p, "key", p) for p in path)
             for path, _ in jax.tree_util.tree_flatten_with_path(template)[0]]

    def build(key):
        edges = jax.lax.map(lambda l: layer_edges(key, cfg, l),
                            jnp.arange(n_layers, dtype=jnp.int32))
        leaves = []
        for path, shape in zip(paths, jax.tree_util.tree_leaves(template)):
            if path == ("embed", "table"):
                leaf = embedding(key, cfg)
            elif path[-1] in ("tau", "s"):
                tau, s = edges["/".join(path[1:-1])]
                leaf = (tau if path[-1] == "tau" else s)[:, None]  # (L, m=1, K, N)
            elif path[-1] in ("scale", "gamma"):
                leaf = jnp.ones(shape.shape, shape.dtype)
            else:
                raise ValueError(f"no weight rule for parameter {'/'.join(map(str, path))}")
            if leaf.shape != shape.shape or leaf.dtype != shape.dtype:
                raise ValueError(f"{'/'.join(map(str, path))}: made {leaf.shape} {leaf.dtype}, "
                                 f"the program wants {shape.shape} {shape.dtype}")
            leaves.append(leaf)
        return jax.tree_util.tree_unflatten(jax.tree_util.tree_structure(template), leaves)

    return jax.block_until_ready(jax.jit(build)(seed_key(seed)))


def build_engine(cfg: dict, seed: int, *, overrides: dict | None = None,
                 mark=lambda phase: None):
    """The paged engine serving this configuration with weights from the
    seed. ``overrides`` replace ArchConfig fields (the control's bfloat16);
    ``mark(phase)`` is called as the weights and then the engine are done."""
    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    sv = cfg["serve"]
    arch = arch_config(cfg)
    if overrides:
        arch = arch.replace(**overrides)
    api = build_model(arch, phase="serve")
    params = make_params(cfg, api, seed)
    mark("weights")
    eng = ServeEngine(api, params, arch, engine=sv["engine"],
                      batch_size=sv["n_slots"], n_slots=sv["n_slots"], max_len=sv["max_len"],
                      quantized_kv=sv["quantized_kv"], kv_block_size=sv["kv_block_size"],
                      kv_n_blocks=sv["kv_n_blocks"], prefix_cache=sv["prefix_cache"],
                      prefill_chunk=sv["prefill_chunk"])
    mark("engine")
    return eng
