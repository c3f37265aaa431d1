"""Profiling hooks for the serving loop (DESIGN.md §8).

Two tools, both opt-in and ~free when disabled:

- ``StepTimer`` — a *sampled* per-tick phase timer. Every ``sample_every``-th
  scheduler tick is sampled: each phase (``admit`` / ``decode`` / ``host``)
  is timed with a monotonic clock and, on the decode phase, the device
  result is ``jax.block_until_ready``-synced inside the phase so the wall
  split attributes device time to decode, not to whichever host line touches
  the array next. Unsampled ticks pay one modulo and a shared null context
  per phase — no clock calls, no allocation. Accumulated phase totals
  extrapolate to a whole-run breakdown (``summary()``), and sampled phases
  optionally stream to a ``Tracer`` as spans on the ``profiler`` track.
- ``profile_trace(log_dir)`` — context manager wrapping a serve window in
  ``jax.profiler.trace`` (XLA/TensorBoard profile, ``--profile-dir`` in
  launch/serve.py); a falsy dir or an unavailable profiler degrades to a
  null context instead of failing the run.
"""
from __future__ import annotations

import contextlib
import time
from typing import Callable, Dict, Optional

__all__ = ["NULL_TIMER", "NullStepTimer", "StepTimer", "profile_trace"]


class _NullCtx:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None


_NULL = _NullCtx()


class _Phase:
    """Times one phase of a sampled tick."""

    __slots__ = ("_timer", "_name", "_t0")

    def __init__(self, timer: "StepTimer", name: str):
        self._timer = timer
        self._name = name

    def __enter__(self):
        self._t0 = self._timer.clock()
        return self

    def __exit__(self, *exc):
        t1 = self._timer.clock()
        self._timer._record(self._name, self._t0, t1)
        return None


class StepTimer:
    """Sampled scheduler-tick phase timer.

    Usage (serve/scheduler.py)::

        prof.tick()                       # decides whether to sample
        with prof.phase("admit"):  ...    # prefill + queue work
        with prof.phase("decode"): prof.sync(step_out)
        with prof.phase("host"):   ...    # emit/EOS bookkeeping
    """

    enabled = True

    def __init__(self, sample_every: int = 16, *, tracer=None,
                 clock: Optional[Callable[[], float]] = None):
        if sample_every < 1:
            raise ValueError(f"sample_every must be >= 1, got {sample_every}")
        self.sample_every = sample_every
        self.tracer = tracer
        # share the tracer clock when there is one, so profiler spans land on
        # the same timeline as the scheduler's request spans
        if clock is None:
            clock = tracer.clock if (tracer is not None and tracer.enabled) \
                else time.perf_counter
        self.clock = clock
        self.ticks = 0
        self.sampled_ticks = 0
        self.sampling = False
        self.phase_s: Dict[str, float] = {}
        self.phase_calls: Dict[str, int] = {}

    def tick(self) -> bool:
        """Advance the tick counter; every ``sample_every``-th tick samples."""
        self.sampling = (self.ticks % self.sample_every) == 0
        self.ticks += 1
        if self.sampling:
            self.sampled_ticks += 1
        return self.sampling

    def phase(self, name: str):
        if not self.sampling:
            return _NULL
        return _Phase(self, name)

    def sync(self, x):
        """Block on device work inside a sampled phase so its wall time is
        attributed here; passthrough when not sampling (the scheduler's host
        loop syncs on its own schedule anyway)."""
        if self.sampling and x is not None:
            import jax

            jax.block_until_ready(x)
        return x

    def _record(self, name: str, t0: float, t1: float) -> None:
        dt = max(t1 - t0, 0.0)
        self.phase_s[name] = self.phase_s.get(name, 0.0) + dt
        self.phase_calls[name] = self.phase_calls.get(name, 0) + 1
        if self.tracer is not None and self.tracer.enabled:
            self.tracer.add_span(name, "profiler", t0, t1, tick=self.ticks - 1)

    def summary(self) -> Dict:
        """Per-phase totals over the sampled ticks + the whole-run
        extrapolation (sampled ticks are an unbiased systematic sample of the
        steady-state loop)."""
        total = sum(self.phase_s.values())
        phases = {
            name: {
                "total_s": self.phase_s[name],
                "calls": self.phase_calls.get(name, 0),
                "mean_s": self.phase_s[name] / max(self.phase_calls.get(name, 1), 1),
                "fraction": (self.phase_s[name] / total) if total > 0 else 0.0,
            }
            for name in sorted(self.phase_s)
        }
        return {
            "ticks": self.ticks,
            "sampled_ticks": self.sampled_ticks,
            "sample_every": self.sample_every,
            "sampled_total_s": total,
            "phases": phases,
        }


class NullStepTimer(StepTimer):
    """Disabled timer: ``tick`` is a no-op and every phase is the shared null
    context — the scheduler's hot loop pays two attribute lookups per tick."""

    enabled = False

    def __init__(self):
        super().__init__(sample_every=1)
        self.sampling = False

    def tick(self) -> bool:
        return False

    def phase(self, name: str):
        return _NULL

    def sync(self, x):
        return x


NULL_TIMER = NullStepTimer()


def profile_trace(log_dir: Optional[str]):
    """``jax.profiler.trace`` context for a serve window (``--profile-dir``).
    Falsy dir -> null context. A profiler that cannot start raises: a run
    asked for a profile does not go on without one."""
    if not log_dir:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.trace(log_dir)
