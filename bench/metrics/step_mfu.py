"""Model step: the operations the served tokens needed in the traced window
(``bench/modelops.py``: 2 per bika edge per token, attention over the live
context, logits where needed; prefix hits, padding and inactive rows not
counted), over the window times the chip's bf16 peak, in percent."""
from bench import modelops, peaks


def read(run):
    w = run.rec.trace_window
    inside = lambda r: r.t0 >= w.open and r.t1 <= w.close
    ops = sum(modelops.decode_ops(run.cfg, t.positions) for t in run.rec.ticks if inside(t))
    ops += sum(modelops.prefill_ops(run.cfg, p.prompt_len, p.cached)
               for p in run.rec.prefills if inside(p))
    if not ops:
        return None
    return 100.0 * ops / (w.seconds * peaks.peaks(run.device_kind)["bf16_flops_per_s"])
