"""Scheduler: mean share of the decode batch's rows doing useful work per
tick over the window (``RunMetrics.slot_occupancy``), in percent."""


def read(run):
    c = run.rec.counters
    return 100.0 * c["slot_occupancy"] if c["decode_steps"] else None
