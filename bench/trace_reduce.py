"""Reduce a JAX profiler trace of the window to the numbers the metrics read.

The trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData``. Each TPU is a plane named ``/device:TPU:<n>``
whose line ``XLA Ops`` holds one event per device operation and whose line
``XLA Modules`` holds one event per executed program (``jit_<function>``).
The host plane ``/host:CPU`` holds the harness's ``TraceAnnotation`` spans
on the Python thread; the ``window`` span bounds the measured window. All
times are nanoseconds on the trace's one clock.

- busy: the union of the device-op intervals inside the window, per chip,
  averaged over the chips; idle share = 1 - busy / window;
- kernel time: the summed durations of the device ops a metric's reader
  picks by their HLO text;
- module time: the summed durations and count of one program's events;
- breakdown: the ten device ops that took most time (by opcode and HLO
  instruction; control flow that holds other ops left out), and the idle
  time inside the window by the innermost host span running at the gap.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import os
from collections import defaultdict
from typing import Callable, Dict, List, Tuple

HOST_SPANS = ("window", "tick", "prefill", "decode", "submit", "wait_arrival")


@dataclasses.dataclass
class Event:
    name: str
    start: float  # ns
    end: float  # ns


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    ops: List[Event]  # device ops inside the window, every chip
    modules: List[Event]
    n_chips: int
    breakdown: dict

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def kernel_s(self, match: Callable[[str], bool]) -> Tuple[float, int]:
        """Device seconds and count of the ops whose name ``match`` accepts."""
        hits = [e for e in self.ops if match(e.name)]
        return sum(e.end - e.start for e in hits) * 1e-9, len(hits)

    def module_s(self, prefix: str) -> Tuple[float, int]:
        """Device seconds and count of the programs named ``prefix*``."""
        hits = [e for e in self.modules if e.name.startswith(prefix)]
        return sum(e.end - e.start for e in hits) * 1e-9, len(hits)


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float) -> List[Tuple[float, float]]:
    out, at = [], lo
    for s, e in busy:
        if s > at:
            out.append((at, min(s, hi)))
        at = max(at, e)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(s, e) for s, e in out if e > s]


def labeller(spans: List[Event]):
    """A function of time -> name of the innermost host span running then
    (``none`` outside every span). Spans on one thread nest, so a stack
    sweep cuts the timeline into pieces, each with one innermost span."""
    cuts: List[Tuple[float, str]] = []  # (piece start, label)
    stack: List[Event] = []
    for sp in sorted(spans, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1].end <= sp.start:
            top = stack.pop()
            cuts.append((top.end, stack[-1].name if stack else "none"))
        stack.append(sp)
        cuts.append((sp.start, sp.name))
    while stack:
        top = stack.pop()
        cuts.append((top.end, stack[-1].name if stack else "none"))
    starts = [c[0] for c in cuts]

    def label(t: float) -> str:
        i = bisect.bisect_right(starts, t) - 1
        return cuts[i][1] if i >= 0 else "none"

    return label


CONTAINERS = ("while", "conditional", "call")  # ops that hold other ops


def opcode(name: str) -> str:
    """The HLO opcode of a device op, whose trace name is its HLO text
    (``%fusion.3 = f32[8,128]{1,0} fusion(...), kind=kLoop``)."""
    _, eq, rest = name.partition(" = ")
    if not eq:
        return name
    if rest.startswith("("):  # a tuple shape: skip to its closing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:
        rest = rest.partition(" ")[2]
    return rest.strip().partition("(")[0]


def short(name: str) -> str:
    """``<opcode> <instruction>`` of a device op, for the breakdown."""
    return f"{opcode(name)} {name.partition(' = ')[0]}"[:120]


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def reduce_planes(planes) -> Reduced:
    """``planes``: objects with ``name`` and ``lines`` (each with ``name``
    and ``events`` carrying ``name``, ``start_ns``, ``duration_ns``)."""
    host: List[Event] = []
    devices: Dict[str, Dict[str, List[Event]]] = {}
    for pl in planes:
        if pl.name == "/host:CPU":
            for ln in pl.lines:
                host.extend(e for e in _events(ln) if e.name in HOST_SPANS)
        elif pl.name.startswith("/device:TPU:") and pl.name[len("/device:TPU:"):].isdigit():
            devices[pl.name] = {ln.name: _events(ln) for ln in pl.lines}
    windows = [e for e in host if e.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans, not one")
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    lo, hi = windows[0].start, windows[0].end
    clip = lambda evs: [Event(e.name, max(e.start, lo), min(e.end, hi))
                        for e in evs if e.end > lo and e.start < hi]
    ops: List[Event] = []
    modules: List[Event] = []
    busy_ns = 0.0
    idle_by_span: Dict[str, float] = defaultdict(float)
    label = labeller([e for e in host if e.name != "window"])
    for lines in devices.values():
        dev_ops = clip(lines.get("XLA Ops", []))
        ops.extend(dev_ops)
        modules.extend(clip(lines.get("XLA Modules", [])))
        busy = union([(e.start, e.end) for e in dev_ops])
        busy_ns += sum(e - s for s, e in busy)
        for s, e in gaps(busy, lo, hi):
            idle_by_span[label((s + e) / 2)] += (e - s) * 1e-9
    n = len(devices)
    by_op: Dict[str, float] = defaultdict(float)
    for e in ops:
        if opcode(e.name) not in CONTAINERS:
            by_op[short(e.name)] += (e.end - e.start) * 1e-9 / n
    top = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    breakdown = {"device_ops": top(by_op),
                 "idle_gaps": top({k: v / n for k, v in idle_by_span.items()})}
    return Reduced(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / n, ops=ops,
                   modules=modules, n_chips=n, breakdown=breakdown)


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if len(found) != 1:
        raise FileNotFoundError(f"{trace_dir}: {len(found)} xplane files, not one")
    return found[0]


def reduce_file(path: str) -> Reduced:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes)


def reduce_dir(trace_dir: str) -> Reduced:
    return reduce_file(find_xplane(trace_dir))
