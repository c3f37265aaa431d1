"""``jax.profiler`` hook for the serving loop (DESIGN.md §8).

``profile_trace(log_dir)`` wraps a serve window in ``jax.profiler.trace``
(``--profile-dir`` in launch/serve.py). The serve loop's own spans
(``Tracer.span``, obs/trace.py) carry the host side of the trace, so the
profiler's Python tracer, which records every Python call, is off.
"""
from __future__ import annotations

import contextlib
from typing import Optional

__all__ = ["profile_trace"]


def profile_trace(log_dir: Optional[str]):
    """``jax.profiler.trace`` context for a serve window (``--profile-dir``).
    Falsy dir -> null context. A profiler that cannot start raises: a run
    asked for a profile does not go on without one."""
    if not log_dir:
        return contextlib.nullcontext()
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return jax.profiler.trace(log_dir, profiler_options=opts)
