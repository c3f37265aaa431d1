"""Model step: device-idle ms per decode tick in the traced window while
the innermost host span feeds the device or reads it back
(``serve.upload``, ``serve.dispatch``, ``serve.token_pull``,
``serve.prefill_chunk``, ``serve.first_token``;
``bench/program_spans.py``), over the ``jit_tick`` programs that
``decode_tick_ms`` counts."""
from bench import program_spans


def read(run):
    return program_spans.per_tick_ms(run, program_spans.DISPATCH)
