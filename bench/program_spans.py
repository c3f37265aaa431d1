"""Device idle time in the traced window, by the program's own host spans.

The serve loop opens a ``jax.profiler.TraceAnnotation`` for each part of
its host work (``src/repro/obs/trace.py``): ``serve.tick``,
``serve.admit``, ``serve.kv_reserve``, ``serve.prefill_chunk``,
``serve.first_token``, ``serve.kv_register``, ``serve.upload``,
``serve.dispatch``, ``serve.token_pull``, ``serve.emit``. They lie on the
``/host:CPU`` plane beside the harness's spans (``bench/trace_reduce.py``),
on the device ops' clock.

Here the window is cut into pieces, each labelled with the innermost
program span running then, else the innermost harness span, else ``none``.
Each idle gap of the device (``trace_reduce.gaps`` of the union of its op
intervals) is split exactly between the pieces it overlaps, where the
reduction's breakdown gives a whole gap to the span at its midpoint: a
decode tick's gap runs through the token pull, the emit loop, admission
and the next upload, and each gets its share. The seconds are averaged over
the chips, as the reduction's are, so the labels sum to its idle time.

A traced run prints the split and its five longest gaps on stderr. A
program without these spans (an older commit) leaves the trace without
them, and ``split`` returns None.
"""
from __future__ import annotations

import bisect
import dataclasses
import functools
import os
import sys
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

from bench import manifest
from bench.trace_reduce import HOST_SPANS, Event, find_xplane, gaps, labeller, union

PREFIX = "serve."
# the host's scheduling work, and the host's work to feed the device and
# read it back
SCHED = ("serve.tick", "serve.admit", "serve.kv_reserve", "serve.kv_register", "serve.emit")
DISPATCH = ("serve.upload", "serve.dispatch", "serve.token_pull", "serve.prefill_chunk",
            "serve.first_token")
# idle time neither metric is for: waiting for traffic, the generator's
# submissions, outside every span
NOT_SERVING = ("wait_arrival", "submit", "none")


def _device(name: str) -> bool:
    return name.startswith("/device:TPU:") and name[len("/device:TPU:"):].isdigit()


@dataclasses.dataclass
class SpanIdle:
    """The idle gaps of a traced window, each split by piece label."""
    n_chips: int
    window: Tuple[float, float]  # ns
    gaps: List[Tuple[float, float, Dict[str, float]]]  # start, end, ns by label; every chip

    def by_label(self) -> Dict[str, float]:
        """Idle seconds by label, averaged over the chips."""
        out: Dict[str, float] = defaultdict(float)
        for _, _, split in self.gaps:
            for k, v in split.items():
                out[k] += v * 1e-9 / self.n_chips
        return dict(out)

    def longest(self, k: int) -> List[Tuple[float, float, Dict[str, float]]]:
        """The ``k`` longest gaps: seconds, seconds into the window, and
        seconds by label."""
        top = sorted(self.gaps, key=lambda g: g[0] - g[1])[:k]
        return [((e - s) * 1e-9, (s - self.window[0]) * 1e-9,
                 {k: v * 1e-9 for k, v in split.items()}) for s, e, split in top]


def split(planes) -> Optional[SpanIdle]:
    """Each idle gap of the device inside the window, split by piece label;
    None when no program span runs in the window. ``planes`` as
    ``trace_reduce.reduce_planes`` takes them."""
    program: List[Event] = []
    harness: List[Event] = []
    devices: List[List[Event]] = []
    for pl in planes:
        if pl.name == "/host:CPU":
            for ln in pl.lines:
                for e in ln.events:
                    name = e.name.partition("#")[0]
                    if name.startswith(PREFIX) or name in HOST_SPANS:
                        ev = Event(name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                        (program if name.startswith(PREFIX) else harness).append(ev)
        elif _device(pl.name):
            devices.append([Event(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
                            for ln in pl.lines if ln.name == "XLA Ops" for e in ln.events])
    windows = [e for e in harness if e.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"the trace holds {len(windows)} window spans, not one")
    if not devices:
        raise ValueError("the trace holds no TPU plane")
    lo, hi = windows[0].start, windows[0].end
    if not any(e.end > lo and e.start < hi for e in program):
        return None
    harness = [e for e in harness if e.name != "window"]
    inner_program, inner_harness = labeller(program), labeller(harness)
    # between two consecutive span edges the innermost span is one span
    edges = sorted({lo, hi} | {t for e in program + harness for t in (e.start, e.end)
                               if lo < t < hi})
    labels = []
    for a, b in zip(edges, edges[1:]):
        label = inner_program((a + b) / 2)
        labels.append(label if label != "none" else inner_harness((a + b) / 2))
    out = []
    for ops in devices:
        busy = union([(max(e.start, lo), min(e.end, hi)) for e in ops
                      if e.end > lo and e.start < hi])
        for s, e in gaps(busy, lo, hi):
            by: Dict[str, float] = defaultdict(float)
            i = bisect.bisect_right(edges, s) - 1
            while i < len(labels) and edges[i] < e:
                by[labels[i]] += min(e, edges[i + 1]) - max(s, edges[i])
                i += 1
            out.append((s, e, dict(by)))
    return SpanIdle(len(devices), (lo, hi), out)


def _cell(run) -> Optional[str]:
    """The cell whose configuration and traffic the run served."""
    bench = manifest.load()
    for w in bench["workloads"]:
        if (manifest.traffic_file(w["traffic"]) == run.traffic
                and manifest.config_file(bench, w["config"]) == run.cfg):
            return w["name"]
    return None


@functools.lru_cache(maxsize=1)
def _read(path: str, mtime_ns: int, ticks: int) -> Optional[Tuple[Tuple[str, float], ...]]:
    from jax.profiler import ProfileData

    sp = split(ProfileData.from_file(path).planes)
    if sp is None:
        return None
    idle = sp.by_label()
    serving = sum(v for k, v in idle.items() if k not in NOT_SERVING)
    covered = sum(idle.get(k, 0.0) for k in SCHED + DISPATCH)
    ms = lambda d: ", ".join(f"{k} {1e3 * v:.3f}" for k, v in
                             sorted(d.items(), key=lambda kv: -kv[1]))
    log = lambda line: print(line, file=sys.stderr)
    log(f"idle by program span (ms, over {ticks} decode ticks): {ms(idle)}; the metrics' "
        f"spans hold {1e3 * covered:.3f} of the {1e3 * serving:.3f} ms idle outside "
        f"{', '.join(NOT_SERVING)}")
    for secs, at, by in sp.longest(5):
        log(f"idle gap of {1e3 * secs:.3f} ms at {at:.3f} s into the window: {ms(by)}")
    return tuple(idle.items())


def run_idle(run) -> Optional[Dict[str, float]]:
    """Idle seconds by label (``split``) of the trace that ``bench/run.py``
    wrote for this run (``.bench_work/trace-<cell>``), read once for all its
    readers."""
    from bench.run import WORK_DIR

    cell = _cell(run)
    if cell is None:
        return None
    try:
        path = find_xplane(os.path.join(WORK_DIR, f"trace-{cell}"))
    except FileNotFoundError:
        return None
    idle = _read(path, os.stat(path).st_mtime_ns, run.trace.module_s("jit_tick")[1])
    return None if idle is None else dict(idle)


def per_tick_ms(run, names) -> Optional[float]:
    """Idle ms under the program spans ``names`` per decode tick (the
    ``jit_tick`` programs that ``decode_tick_ms`` counts) in the window."""
    idle = run_idle(run)
    _, n = run.trace.module_s("jit_tick")
    if idle is None or not n:
        return None
    return 1e3 * sum(idle.get(k, 0.0) for k in names) / (n / run.trace.n_chips)
