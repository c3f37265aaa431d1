"""Drive the paged engine with open-loop traffic over a fixed window.

Requests are submitted when they fall due, whatever the engine is doing
(an open loop: independent users), and the engine ticks in between. Every
call into the engine runs inside a ``jax.profiler.TraceAnnotation`` (the
harness's host spans: ``tick``, ``prefill``, ``decode``, ``submit``,
``wait_arrival``), so a traced run can say what the host was doing in each
idle gap of the device. Two calls are wrapped to record the work they do,
for the operation counts of ``step_mfu`` and ``paged_attn_roofline``: the
scheduler's chunked prefill (request, cached prefix, prompt length) and its
decode tick (the live position of every active row).

The window opens ``warm_s`` after traffic starts, or, for a cell that runs
above capacity (``opens: saturated``), after the first tick whose admission
left requests waiting because every slot or the pool's blocks were taken.
It closes ``seconds`` later. With ``drain: first_tokens`` the engine keeps
serving (arrivals included) until every request due in the window has its
first token, up to ``drain_limit_s``.

Set-up ends when the traffic starts (``RunRecord.t_traffic``), so the
traffic's warm-up or ramp (``ramp_s``) is not set-up. Every backend compile
from then to the end of serving is counted with its seconds
(``compile_s``), which the harness adds to set-up: program work that moves
out of set-up into the first seconds of traffic still shows there.
"""
from __future__ import annotations

import collections
import dataclasses
import time
from typing import Callable, Dict, List, Optional

import jax
import numpy as np

from bench.stats import ReqRecord, Window

CHECK_WAIT_S = 60.0  # longest wait past the drain for requests to finish


@dataclasses.dataclass
class TickRecord:
    t0: float
    t1: float
    positions: List[int]  # write position of each active row (live context - 1)


@dataclasses.dataclass
class PrefillRecord:
    t0: float
    t1: float
    rid: int
    prompt_len: int
    cached: int
    chunk: int


@dataclasses.dataclass
class RunRecord:
    requests: Dict[int, ReqRecord]
    ticks: List[TickRecord]
    prefills: List[PrefillRecord]
    window: Window
    t_traffic: float  # host time traffic started (set-up ends here)
    ramp_s: float  # traffic start -> window open
    drain_s: float  # window close -> serving stopped
    t_stop: float  # host time the drain ended (missing first tokens count to here)
    check_wait_s: float  # drained -> stopped, waiting for requests to finish
    trace_window: Optional[Window]  # the traced part of the window
    counters: Dict[str, float]  # the program's RunMetrics over the window
    outputs: Dict[int, np.ndarray]  # tokens of every finished request
    compiles_in_window: int
    compiles_in_traffic: int  # compiles from traffic start to the end of serving
    compile_s: float  # their summed seconds, which count as set-up
    late_submit_p95_s: float  # how late the generator submitted, p95


class _PrefillTap:
    """Stands in for the scheduler's ``ChunkedPrefill``: records each
    admission's work and forwards everything else."""

    def __init__(self, inner, clock, log: List[PrefillRecord]):
        self._inner, self._clock, self._log = inner, clock, log

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def __call__(self, params, cache, table_row, prompt, cached_len=0, **kw):
        t0 = self._clock()
        with jax.profiler.TraceAnnotation("prefill"):
            out = self._inner(params, cache, table_row, prompt, cached_len, **kw)
        self._log.append(PrefillRecord(t0, self._clock(), kw.get("rid", -1), len(prompt),
                                       int(cached_len), self._inner.chunk))
        return out


class _CompileCounter:
    """Counts the backend compiles of the process and sums their seconds."""

    def __init__(self):
        self.n = 0
        self.secs = 0.0

    def __call__(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.secs += secs


class Driver:
    def __init__(self, engine, clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep):
        self.sched = engine.scheduler
        self.clock, self.sleep = clock, sleep
        self.sched.clock = clock
        self.saturated_at = None  # (slots busy, blocks in use) when saturated
        self.ticks: List[TickRecord] = []
        self.prefills: List[PrefillRecord] = []
        self.sched.prefill = _PrefillTap(self.sched.prefill, clock, self.prefills)
        inner_tick = self.sched._run_tick

        def run_tick():
            pos = [int(self.sched._pos[i]) for i, s in enumerate(self.sched._slots)
                   if s is not None]
            t0 = clock()
            with jax.profiler.TraceAnnotation("decode"):
                out = inner_tick()
            self.ticks.append(TickRecord(t0, clock(), pos))
            return out

        self.sched._run_tick = run_tick
        # the listener lives as long as the process: it holds the counter
        # alone, never the engine
        self._compiles = _CompileCounter()
        jax.monitoring.register_event_duration_secs_listener(self._compiles)

    @property
    def compiles(self) -> int:
        return self._compiles.n

    def warm_up(self, prompt_len: int, new_tokens: int, vocab: int) -> None:
        """Compile and run the cell's two programs (the prefill chunk and
        the decode tick) on one throwaway request; forgets its records."""
        from repro.serve.scheduler import Request

        rng = np.random.default_rng(0)
        self.sched.submit(Request(rid=-1, prompt=rng.integers(0, vocab, prompt_len,
                                                               dtype=np.int32),
                                  max_new_tokens=new_tokens))
        while self.sched.has_work:
            self.sched.tick()
        self.sched.completed.clear()
        self.ticks.clear()
        self.prefills.clear()

    def run(self, items, seconds: float, window_cfg: dict, *,
            trace_start: Optional[Callable[[], None]] = None,
            trace_stop: Optional[Callable[[], None]] = None,
            trace_s: float = 0.0, min_check_tokens: int = 0) -> RunRecord:
        """Serve ``items`` open loop and measure a window of ``seconds``.
        ``trace_start``/``trace_stop`` bracket the first ``trace_s`` seconds
        of the window. After the window (and any drain), serving goes on
        until the finished requests hold ``min_check_tokens`` served tokens
        for the correctness check, for at most ``CHECK_WAIT_S``."""
        from repro.serve.scheduler import Request

        sched, clock = self.sched, self.clock
        recs: Dict[int, ReqRecord] = {}
        reqs: Dict[int, Request] = {}
        pending = collections.deque(items)
        late: List[float] = []
        opens = window_cfg["opens"]
        t_start = clock()
        compiles_start, compile_s_start = self._compiles.n, self._compiles.secs
        t_open = t_start + float(window_cfg.get("warm_s", 0)) if opens == "after_warm" else None
        if opens not in ("after_warm", "saturated"):
            raise ValueError(f"unknown window opening {opens!r}")
        t_close = t_open + seconds if t_open is not None else None
        opened = closed = False
        drain = window_cfg.get("drain", "none")
        ramp_limit = float(window_cfg.get("ramp_limit_s", 0))
        drain_limit = float(window_cfg.get("drain_limit_s", 0))
        compiles0 = counters = t_trace_end = t_trace0 = None
        tracing = False
        t_drained = None
        while True:
            now = clock()
            while pending and t_start + pending[0].due_s <= now:
                it = pending.popleft()
                rec = ReqRecord(it.rid, t_start + it.due_s, len(it.prompt), it.max_new_tokens)
                recs[it.rid] = rec
                reqs[it.rid] = Request(
                    rid=it.rid, prompt=it.prompt, max_new_tokens=it.max_new_tokens,
                    on_token=lambda tok, r=rec: r.token_times.append(clock()))
                with jax.profiler.TraceAnnotation("submit"):
                    rec.t_submit = clock()
                    sched.submit(reqs[it.rid])
                late.append(rec.t_submit - rec.due)
            if t_open is None and now - t_start > ramp_limit:
                raise RuntimeError(f"the engine was not saturated {ramp_limit} s into the "
                                   f"traffic ({sched.n_active}/{sched.n_slots} slots busy)")
            if not opened and t_open is not None and now >= t_open:
                # the window opens at a tick boundary, so the program's
                # counters and the trace cover exactly the window
                opened = True
                t_open, t_close = now, now + seconds
                sched.reset_metrics()
                compiles0 = self.compiles
                if trace_start is not None:
                    trace_start()
                    tracing = True
                    t_trace0 = clock()
            if tracing and now >= t_trace0 + trace_s:
                trace_stop()
                tracing = False
                t_trace_end = clock()
            if opened and not closed and now >= t_close:
                closed = True
                m = sched.metrics
                counters = {"slot_occupancy": m.slot_occupancy,
                            "prefix_hit_tokens": m.prefix_hit_tokens,
                            "prefix_prompt_tokens": m.prefix_prompt_tokens,
                            "decode_steps": m.decode_steps, "prefills": m.prefills,
                            "prefill_chunks": m.prefill_chunks}
                compiles_in_window = self.compiles - compiles0
            if closed:
                w = Window(t_open, t_close)
                waiting = drain == "first_tokens" and any(
                    w.holds(r.due) and not r.token_times for r in recs.values())
                if t_drained is None and not (waiting and now - t_close <= drain_limit):
                    t_drained = now
                short = sum(len(r.output) for r in sched.completed) < min_check_tokens
                if t_drained is not None and (not short or now - t_drained > CHECK_WAIT_S):
                    break
            if sched.has_work:
                with jax.profiler.TraceAnnotation("tick"):
                    sched.tick()
                if t_open is None and sched.queue:
                    # admission left requests waiting: every slot, or the
                    # pool's blocks, are taken
                    t_open = clock()
                    t_close = t_open + seconds
                    self.saturated_at = (sched.n_active, sched.kv.blocks_in_use)
            elif pending:
                with jax.profiler.TraceAnnotation("wait_arrival"):
                    self.sleep(max(0.0, t_start + pending[0].due_s - clock()))
            elif closed:
                break
            else:
                raise RuntimeError("traffic ran out before the window closed")
        t_stop = clock()
        if tracing:
            raise RuntimeError("serving stopped before the traced seconds ended")
        outputs = {r.rid: np.asarray(r.output) for r in sched.completed}
        cached = {p.rid: p.cached for p in self.prefills}
        for rid, rec in recs.items():
            rec.t_admit = reqs[rid].metrics.t_admit  # the scheduler's own stamp
            rec.cached_tokens = cached.get(rid)
        late.sort()
        return RunRecord(
            requests=recs, ticks=list(self.ticks), prefills=list(self.prefills),
            window=Window(t_open, t_close), t_traffic=t_start, ramp_s=t_open - t_start,
            drain_s=(t_drained or t_stop) - t_close, t_stop=t_drained or t_stop,
            check_wait_s=t_stop - (t_drained or t_stop),
            trace_window=Window(t_trace0, t_trace_end) if t_trace_end else None,
            counters=counters, outputs=outputs, compiles_in_window=compiles_in_window,
            compiles_in_traffic=self._compiles.n - compiles_start,
            compile_s=self._compiles.secs - compile_s_start,
            late_submit_p95_s=late[min(len(late) - 1, int(0.95 * len(late)))] if late else 0.0)
