"""Scheduler: device-idle ms per decode tick in the traced window while the
innermost host span is the scheduler's own work (``serve.tick``'s self
time, ``serve.admit``, ``serve.kv_reserve``, ``serve.kv_register``,
``serve.emit``; ``bench/program_spans.py``), over the ``jit_tick``
programs that ``decode_tick_ms`` counts."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, program_spans.SCHED)
