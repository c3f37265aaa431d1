"""Operations a bika llama-style LM step needs, per token, for ``step_mfu``.

Each bika edge costs 2 operations per token it processes (a comparison and
an accumulation, counted as a multiply-accumulate is), so one yardstick
serves every backend. Attention counts the live context
(``bench/kernels/paged_attn.py``), and the unembedding ``2 * d * vocab``
only where logits are needed: every decode row, and the last token of each
prompt. Prefix-cache hits, padding and inactive slot rows are not work the
algorithm needs.
"""
from __future__ import annotations

from bench import model
from bench.kernels import paged_attn


def edges_per_layer(cfg: dict) -> int:
    return sum(k * n for k, n in model.projection_shapes(cfg).values())


def token_ops(cfg: dict, position: int, logits: bool) -> float:
    """Operations to process one token at ``position`` through every layer."""
    hd = model.head_dim(cfg)
    attn, _ = paged_attn.chunk_call(position, 1, cfg["num_attention_heads"],
                                    cfg["num_key_value_heads"], hd)
    per_layer = 2.0 * edges_per_layer(cfg) + attn
    head = 2.0 * cfg["hidden_size"] * cfg["vocab_size"] if logits else 0.0
    return cfg["num_hidden_layers"] * per_layer + head


def prefill_ops(cfg: dict, prompt_len: int, cached: int) -> float:
    """Operations of one admission: the prompt tokens past the cached
    prefix, and logits for the last one."""
    return sum(token_ops(cfg, p, logits=(p == prompt_len - 1))
               for p in range(cached, prompt_len))


def decode_ops(cfg: dict, positions) -> float:
    """Operations of one decode tick over the active rows' positions."""
    return sum(token_ops(cfg, p, logits=True) for p in positions)
