"""``kernels/paged_attn.py`` (the fused paged-attention Pallas kernel):
the operations and HBM bytes one call needs, from the live context alone.

One call is one layer. A query row at logical position ``p`` attends the
``p + 1`` keys up to it: ``2 * D`` operations per key and query head for
``q . k`` and as many for ``p @ v``; the keys and values of those positions
are read once per kv head, and the query and output rows once. Inactive
slot rows, padding and positions past a row's own are work the algorithm
does not need, and are not counted.
"""
from __future__ import annotations

from typing import Iterable, Tuple


def decode_call(positions: Iterable[int], hq: int, hkv: int, d: int,
                kv_bytes: int = 4) -> Tuple[float, float]:
    """(ops, bytes) of one decode call; ``positions`` holds the write
    position of each active row (it attends ``position + 1`` keys)."""
    ops = bts = 0.0
    for p in positions:
        live = p + 1
        ops += 4.0 * hq * d * live
        bts += 2.0 * live * hkv * d * kv_bytes + 2.0 * hq * d * 4
    return ops, bts


def chunk_call(start: int, n_real: int, hq: int, hkv: int, d: int,
               kv_bytes: int = 4) -> Tuple[float, float]:
    """(ops, bytes) of one prefill-chunk call: ``n_real`` prompt tokens at
    positions ``start ..``, each attending every position up to its own."""
    keys = sum(start + t + 1 for t in range(n_real))
    ops = 4.0 * hq * d * keys
    bts = 2.0 * (start + n_real) * hkv * d * kv_bytes + 2.0 * n_real * hq * d * 4
    return ops, bts
