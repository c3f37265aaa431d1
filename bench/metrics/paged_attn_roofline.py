"""Kernels: the fused paged-attention kernel's share of its roofline over
the traced window, in percent. Operations and bytes come from the live
context of every call in the window (``bench/kernels/paged_attn.py``: the
decode ticks' active rows and the prefill chunks' real tokens, one call per
layer); the time is the summed device time of the kernel's ops in the
trace. The ops are found by the kernel's name: the ``tpu_custom_call`` ops
whose HLO instruction is named ``paged_attention`` (the ``pallas_call``'s
name), whatever layout the KV pool has. The least time is the larger of
operations over the chip's bf16 peak, its one published matmul peak (at
"highest" the kernel's f32 dots take several bf16 passes, so this bound is
the lower one), and bytes over HBM bandwidth."""
import sys

from bench import model, peaks
from bench.kernels import paged_attn

KERNEL = "paged_attention"  # the pallas_call's name in kernels/paged_attn.py


def is_kernel(op: str) -> bool:
    """Whether a device op (its HLO text) is a call of the kernel: a
    ``tpu_custom_call`` whose instruction is ``%paged_attention[.<n>]``."""
    inst = op.partition(" = ")[0].lstrip("%")
    return ('custom_call_target="tpu_custom_call"' in op
            and (inst == KERNEL or inst.startswith(KERNEL + ".")))


def read(run):
    cfg, w = run.cfg, run.rec.trace_window
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], model.head_dim(cfg)
    secs, n = run.trace.kernel_s(is_kernel)
    if not n:
        return None
    layers = cfg["num_hidden_layers"]
    inside = lambda r: r.t0 >= w.open and r.t1 <= w.close
    ops = bts = 0.0
    for t in run.rec.ticks:
        if inside(t):
            o, b = paged_attn.decode_call(t.positions, hq, hkv, d)
            ops, bts = ops + layers * o, bts + layers * b
    for p in run.rec.prefills:
        if inside(p):
            for s in range(p.cached, p.prompt_len, p.chunk):
                o, b = paged_attn.chunk_call(s, min(p.chunk, p.prompt_len - s), hq, hkv, d)
                ops, bts = ops + layers * o, bts + layers * b
    pk = peaks.peaks(run.device_kind)
    t_ops, t_bytes = ops / pk["bf16_flops_per_s"], bts / pk["hbm_bytes_per_s"]
    print(f"paged_attn: {n} calls, {secs} s on the device, {ops} ops, {bts} bytes; "
          f"bound by {'compute' if t_ops >= t_bytes else 'HBM bandwidth'}", file=sys.stderr)
    return 100.0 * max(t_ops, t_bytes) / secs
