"""Metric arithmetic over one run's host-clock records.

Adapted from ``serve/metrics.py`` (``_percentile``, queue wait, occupancy),
with the two changes its definitions need for an open loop:

- time to first token is timed from the request's *due* time, not from its
  submission, so a tick that runs long counts against the requests that
  waited behind it;
- the inter-token gap is every gap between two consecutive tokens of a
  request, pooled over all requests, not a per-request mean.

All times are seconds on one host clock (``time.perf_counter``).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List, Optional, Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Ceil-based nearest rank: the first value whose rank covers ``q``
    (never under-reports a tail). Empty input has no percentile."""
    if not values:
        raise ValueError("percentile of no values")
    vals = sorted(values)
    rank = math.ceil(q * (len(vals) - 1) - 1e-9)
    return vals[min(len(vals) - 1, max(0, rank))]


@dataclasses.dataclass
class ReqRecord:
    rid: int
    due: float  # absolute host time the request was due
    prompt_len: int
    max_new_tokens: int
    token_times: List[float] = dataclasses.field(default_factory=list)
    t_submit: Optional[float] = None
    t_admit: Optional[float] = None
    cached_tokens: Optional[int] = None


@dataclasses.dataclass
class Window:
    open: float
    close: float

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def holds(self, t: float) -> bool:
        return self.open <= t < self.close


def due_in_window(recs: Sequence[ReqRecord], w: Window) -> List[ReqRecord]:
    return [r for r in recs if w.holds(r.due)]


def ttft_values(recs: Sequence[ReqRecord], w: Window, t_stop: float) -> List[float]:
    """Time to first token of every request due in the window, from its due
    time. A request with no first token by ``t_stop`` (the end of serving)
    is missing and counts as the whole wait, ``t_stop - due``: a lower bound
    that is at least the drain limit."""
    return [(r.token_times[0] if r.token_times else t_stop) - r.due
            for r in due_in_window(recs, w)]


def itl_gaps(recs: Sequence[ReqRecord], w: Window) -> List[float]:
    """Every gap between consecutive tokens of one request whose later
    token was emitted inside the window, pooled over requests."""
    out = []
    for r in recs:
        t = r.token_times
        out.extend(t[i] - t[i - 1] for i in range(1, len(t)) if w.holds(t[i]))
    return out


def tokens_in_window(recs: Sequence[ReqRecord], w: Window) -> int:
    return sum(1 for r in recs for t in r.token_times if w.holds(t))


def queue_waits(recs: Sequence[ReqRecord], w: Window) -> List[float]:
    """Due time -> slot claimed, for requests due in the window that were
    admitted (the scheduler's queue wait, timed from the due time)."""
    return [r.t_admit - r.due for r in due_in_window(recs, w) if r.t_admit is not None]


def end_to_end(recs: Sequence[ReqRecord], w: Window, t_stop: float) -> Dict[str, float]:
    """The run's end-to-end numbers, under the names BENCHMARK.json uses."""
    out = {"out_tok_s": tokens_in_window(recs, w) / w.seconds}
    gaps = itl_gaps(recs, w)
    if gaps:
        out["itl_p95_ms"] = 1e3 * percentile(gaps, 0.95)
    ttft = ttft_values(recs, w, t_stop)
    if ttft:
        out["ttft_p95_s"] = percentile(ttft, 0.95)
    return out
