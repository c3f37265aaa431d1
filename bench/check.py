"""Whether what the timed path served is correct.

Once the window has closed and the engine is freed, a sample of the
requests the engine finished, drawn from the seed and always holding the
longest, is packed into rows of the reference's length. The plain reference
(``bench/reference.py``) runs once over each row: every prompt followed by
the tokens served for it. Served tokens are greedy, so each should be the
reference's best at its position; the number compared is the widest gap by
which a served token's reference logit lies below the reference's best
(``max_gap``, in logits); the mean of those gaps over every served token
compared is ``mean_gap``. A third number counts finished requests whose
output is not the length they asked for (``wrong_length``).

Which numbers a cell compares, and their limits, are the cell's own
(``bench/cells/<cell>.json``), set from what the sound program and its
control read in that cell; the others are printed on an earlier line.

In a cell with shared prefixes the sample also holds a request that missed
the prefix cache and one that hit it, so a prefix hit is compared as well
as a miss.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import numpy as np


def expected_len(prompt_len: int, max_new: int, max_len: int) -> int:
    """Tokens the scheduler serves: its budget is capped at the cache end."""
    return min(max_new, max_len - prompt_len + 1)


def pick_rows(lengths: Dict[int, int], row_tokens: int, n_rows: int, rng,
              must: Sequence[int] = ()) -> List[List[int]]:
    """Pack requests (id -> sequence length) into at most ``n_rows`` rows of
    ``row_tokens``: the longest first, then ``must``, then the rest in an
    order drawn from ``rng``, each into the first row it fits."""
    fits = {r: n for r, n in lengths.items() if n <= row_tokens}
    if not fits:
        return []
    longest = max(fits, key=lambda r: (fits[r], -r))
    rest = [r for r in rng.permutation(sorted(fits)) if r != longest and r not in must]
    order = [longest] + [r for r in must if r in fits and r != longest] + rest
    rows: List[List[int]] = []
    room: List[int] = []
    for r in order:
        for i, left in enumerate(room):
            if fits[r] <= left:
                rows[i].append(r)
                room[i] -= fits[r]
                break
        else:
            if len(rows) < n_rows:
                rows.append([r])
                room.append(row_tokens - fits[r])
    return rows


def pack_row(rids, prompts, outputs, row_tokens):
    """Tokens, positions and segment ids of one row, and for each request
    the row offsets whose logits predict its served tokens."""
    tokens = np.zeros(row_tokens, np.int32)
    positions = np.zeros(row_tokens, np.int32)
    segments = np.full(row_tokens, -1, np.int32)
    spans = {}
    at = 0
    for seg, r in enumerate(rids):
        seq = np.concatenate([prompts[r], outputs[r][:-1]]).astype(np.int32)
        n = len(seq)
        tokens[at:at + n] = seq
        positions[at:at + n] = np.arange(n)
        segments[at:at + n] = seg
        p = len(prompts[r])
        spans[r] = np.arange(at + p - 1, at + p - 1 + len(outputs[r]))
        at += n
    return tokens, positions, segments, spans


def served_gaps(logits: np.ndarray, spans: dict, outputs: dict) -> Dict[int, np.ndarray]:
    """Per request, each served token's gap below the best logit there."""
    out = {}
    for r, rows in spans.items():
        lg = logits[rows]
        out[r] = lg.max(axis=-1) - lg[np.arange(len(rows)), outputs[r]]
    return out


def compare(cfg: dict, seed: int, prompts: dict, outputs: dict, asked: dict,
            limits: Dict[str, float], must: Sequence[int] = (), ref_logits=None) -> dict:
    """Run the reference over a sample of finished requests; returns the
    numbers compared, each with its limit from ``limits``, the others, and
    what was compared."""
    if ref_logits is None:
        from bench.reference import logits as ref_logits
    chk = cfg["check"]
    max_len = cfg["serve"]["max_len"]
    short = sum(1 for r, out in outputs.items()
                if len(out) != expected_len(len(prompts[r]), asked[r], max_len))
    rng = np.random.default_rng(seed)
    lengths = {r: len(prompts[r]) + len(o) - 1 for r, o in outputs.items() if len(o)}
    rows = pick_rows(lengths, chk["reference_row_tokens"], chk["reference_rows"], rng, must)
    gaps: Dict[int, np.ndarray] = {}
    for rids in rows:
        tokens, positions, segments, spans = pack_row(rids, prompts, outputs,
                                                      chk["reference_row_tokens"])
        lg = np.asarray(ref_logits(cfg, seed, tokens, positions, segments))
        gaps.update(served_gaps(lg, spans, outputs))
    every = np.concatenate(list(gaps.values())) if gaps else np.zeros(0)
    values = {"max_gap": float(every.max()) if every.size else None,
              "mean_gap": float(every.mean()) if every.size else None,
              "wrong_length": short}
    unknown = set(limits) - set(values)
    if unknown:
        raise KeyError(f"the check has no numbers named {sorted(unknown)}")
    numbers = {k: {"value": values[k], "limit": lim} for k, lim in limits.items()}
    correct = every.size > 0 and bool(numbers) and all(
        v["value"] is not None and v["value"] <= v["limit"] for v in numbers.values())
    return {"correct": bool(correct), "numbers": numbers,
            "printed": {k: v for k, v in values.items() if k not in limits},
            "compared_requests": sorted(gaps), "compared_tokens": int(every.size),
            "argmax_agree": float((every == 0).mean()) if every.size else None}


def describe(result: dict) -> List[str]:
    """One line per number compared, with its limit."""
    return [f"check {k}: {v['value']} (limit {v['limit']})"
            for k, v in result["numbers"].items()]


def required_sample(records: dict) -> List[int]:
    """For a cell with shared prefixes: one finished request that missed the
    prefix cache and one that hit it."""
    out: List[int] = []
    for hit in (False, True):
        cands = sorted(r for r, rec in records.items()
                       if rec.cached_tokens is not None and (rec.cached_tokens > 0) == hit)
        if cands:
            out.append(cands[0])
    return out
