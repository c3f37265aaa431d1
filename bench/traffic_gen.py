"""The one traffic generator: a traffic file's parameters -> timed requests.

Adapted from ``benchmarks/serving_bench.make_workload`` (Poisson arrivals,
random token ids), with three changes:

- lengths are heavy-tailed (log-normal or log-uniform, clipped) instead of
  uniform;
- every seed gets the same multiset of sizes and gaps. Requests come in
  blocks of ``block`` whose lengths are the stratified quantiles
  ``(i + 0.5) / block`` of the length distributions and whose gaps are the
  same quantiles of the exponential; the seed only shuffles each block and
  draws the token ids. So two seeds offer the same work in another order,
  and the run-to-run spread measures the system, not the draw;
- prefixes can be shared: ``prefix.kind == "system_prompts"`` prepends one
  of ``count`` system prompts of ``length`` tokens, chosen with Zipf
  weights ``1 / j ** zipf_s``, to a unique suffix.

A traffic file (``bench/traffic/<name>.json``) holds::

    {"rate_per_s": 3.0, "block": 64,
     "prompt": {"dist": "lognormal", "median": 256, "sigma": 1.0, "min": 16, "max": 1536},
     "output": {"dist": "loguniform", "min": 256, "max": 512},
     "prefix": {"kind": "system_prompts", "count": 4, "length": 1024, "zipf_s": 1.1},
     "window": {"opens": "after_warm", "warm_s": 3, "drain": "first_tokens"}}

With a prefix, ``prompt`` is the suffix's length.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import List, Optional

import numpy as np

PAIRING_SEED = 20240611  # fixes how prompt and output quantiles pair up, for every seed


@dataclasses.dataclass
class Item:
    rid: int
    due_s: float  # offset from the start of traffic
    prompt: np.ndarray  # (S,) int32
    max_new_tokens: int
    prefix_id: Optional[int] = None


def quantile_lengths(spec: dict, n: int) -> np.ndarray:
    """The ``(i + 0.5) / n`` quantiles of a length distribution, clipped."""
    u = (np.arange(n) + 0.5) / n
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in u])
        v = spec["median"] * np.exp(spec["sigma"] * z)
    elif spec["dist"] == "loguniform":
        v = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def exponential_gaps(rate_per_s: float, n: int) -> np.ndarray:
    u = (np.arange(n) + 0.5) / n
    return -np.log1p(-u) / rate_per_s


def zipf_counts(count: int, s: float, n: int) -> np.ndarray:
    """How many of ``n`` requests pick each of ``count`` prefixes under
    Zipf weights, by largest remainders so that they sum to ``n``."""
    w = 1.0 / np.arange(1, count + 1) ** s
    exact = n * w / w.sum()
    out = np.floor(exact).astype(np.int64)
    out[np.argsort(-(exact - out))[: n - out.sum()]] += 1
    return out


def generate(traffic: dict, seed: int, vocab: int, n_requests: int) -> List[Item]:
    """``n_requests`` timed requests of this traffic mix, from the seed."""
    block = int(traffic["block"])
    rng = np.random.default_rng(seed)
    pair = np.random.default_rng(PAIRING_SEED).permutation(block)
    plens = quantile_lengths(traffic["prompt"], block)
    olens = quantile_lengths(traffic["output"], block)[pair]
    gaps = exponential_gaps(float(traffic["rate_per_s"]), block)
    prefix = traffic.get("prefix") or {"kind": "none"}
    if prefix["kind"] == "system_prompts":
        sys_prompts = [rng.integers(0, vocab, int(prefix["length"]), dtype=np.int32)
                       for _ in range(int(prefix["count"]))]
        choice = np.repeat(np.arange(int(prefix["count"])),
                           zipf_counts(int(prefix["count"]), float(prefix["zipf_s"]), block))
    elif prefix["kind"] == "none":
        sys_prompts, choice = None, None
    else:
        raise ValueError(f"unknown prefix kind {prefix['kind']!r}")
    items: List[Item] = []
    t = 0.0
    while len(items) < n_requests:
        order = rng.permutation(block)
        gap_order = rng.permutation(block)
        pre_order = rng.permutation(block)
        for j in range(block):
            if len(items) == n_requests:
                break
            t += float(gaps[gap_order[j]])
            body = rng.integers(0, vocab, int(plens[order[j]]), dtype=np.int32)
            pid = None
            if sys_prompts is not None:
                pid = int(choice[pre_order[j]])
                body = np.concatenate([sys_prompts[pid], body])
            items.append(Item(rid=len(items), due_s=t, prompt=body,
                              max_new_tokens=int(olens[order[j]]), prefix_id=pid))
    return items


def requests_needed(traffic: dict, seconds: float) -> int:
    """Enough requests to keep arrivals going through warm-up, the window
    and the longest drain."""
    w = traffic["window"]
    span = float(w.get("warm_s", 0)) + float(w.get("ramp_limit_s", 0)) + seconds + float(
        w.get("drain_limit_s", 0))
    return int(math.ceil(float(traffic["rate_per_s"]) * span)) + int(traffic["block"])
