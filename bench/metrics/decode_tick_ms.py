"""Model step: device time of one decode tick, the jitted ``tick`` program
(``jit_tick`` in the trace), averaged over the ticks in the traced window."""


def read(run):
    secs, n = run.trace.module_s("jit_tick")
    return 1e3 * secs / n if n else None
