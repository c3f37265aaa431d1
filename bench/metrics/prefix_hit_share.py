"""Paged pool: share of the prompt tokens admitted in the window that the
prefix cache served (``RunMetrics.prefix_hit_tokens`` over
``prefix_prompt_tokens``), in percent."""


def read(run):
    c = run.rec.counters
    if not c["prefix_prompt_tokens"]:
        return None
    return 100.0 * c["prefix_hit_tokens"] / c["prefix_prompt_tokens"]
