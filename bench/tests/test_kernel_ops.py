"""Operations and bytes per kernel call and per model step."""
import json
import os

import pytest

from bench import modelops
from bench.kernels import paged_attn

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")


def cfg(name):
    with open(os.path.join(CONFIGS, f"{name}.json")) as f:
        return json.load(f)


def brute_attn(queries, hq, hkv, d, kv_bytes=4):
    """Count per (query, key, head) pair; bytes per distinct key position."""
    ops = sum(4 * d for q in queries for _k in range(q + 1) for _h in range(hq))
    keys = max(queries) + 1
    return ops, 2 * keys * hkv * d * kv_bytes


def test_decode_call_counts_each_rows_live_context():
    ops, bts = paged_attn.decode_call([0, 5, 17], hq=6, hkv=2, d=8)
    assert ops == sum(brute_attn([p], 6, 2, 8)[0] for p in (0, 5, 17))
    assert bts == sum(brute_attn([p], 6, 2, 8)[1] for p in (0, 5, 17)) + 3 * 2 * 6 * 8 * 4


@pytest.mark.parametrize("start,n", [(0, 1), (0, 32), (64, 32), (64, 7)])
def test_chunk_call_is_causal_over_the_real_tokens(start, n):
    ops, bts = paged_attn.chunk_call(start, n, hq=4, hkv=4, d=16)
    want_ops, want_kv = brute_attn(list(range(start, start + n)), 4, 4, 16)
    assert ops == want_ops
    assert bts == want_kv + 2 * n * 4 * 16 * 4


@pytest.mark.parametrize("name,edges", [("smollm-360m-bika", 314_572_800),
                                        ("phi3-mini-3.8b-bika", 3_623_878_656)])
def test_edges_match_the_published_widths(name, edges):
    # the published models have 32 layers; the cells hold one of them
    assert modelops.edges_per_layer(cfg(name)) * 32 == edges


def test_prefill_counts_uncached_tokens_and_one_logits_row():
    c = cfg("smollm-360m-bika")
    full = modelops.prefill_ops(c, 100, 0)
    hit = modelops.prefill_ops(c, 100, 64)
    assert full - hit == pytest.approx(sum(modelops.token_ops(c, p, False) for p in range(64)))
    head = 2 * c["hidden_size"] * c["vocab_size"]
    assert modelops.token_ops(c, 99, True) - modelops.token_ops(c, 99, False) == head
    assert modelops.decode_ops(c, [5, 9]) == pytest.approx(
        modelops.token_ops(c, 5, True) + modelops.token_ops(c, 9, True))
