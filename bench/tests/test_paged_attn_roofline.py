"""``paged_attn_roofline`` finds the kernel by its name, whatever layout the
KV pool has, and no other ``tpu_custom_call``."""
import json
import os
from types import SimpleNamespace as NS

import pytest

from bench import model, peaks, trace_reduce
from bench.kernels import paged_attn
from bench.metrics import paged_attn_roofline
from bench.stats import Window
from bench.tests.test_trace_reduce import ev, plane

CONFIGS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "configs")
TARGET = 'custom_call_target="tpu_custom_call"'
# the KV pool laid out block-minor, [blocks, block, kv heads, head dim], and
# lane-dense, [blocks, kv heads, block * head dim]
BLOCK_MINOR = "f32[8193,16,5,64]{3,2,1,0:T(8,128)}"
LANE_DENSE = "f32[8193,5,1024]{2,1,0:T(8,128)}"


def op(inst, pool, target=TARGET, opname="custom-call"):
    return (f"%{inst} = f32[64,5,3,64]{{3,2,1,0}} {opname}(s32[64,128]{{1,0}} %tables, "
            f"{pool} %k, {pool} %v), {target}")


@pytest.mark.parametrize("name,found", [
    (op("paged_attention.2", BLOCK_MINOR), True),
    (op("paged_attention.2", LANE_DENSE), True),
    (op("paged_attention", LANE_DENSE), True),
    # the kernel before it had a name (the recorded trace's), and others
    (op("closed_call.2", BLOCK_MINOR), False),
    (op("paged_attention_prefill.1", LANE_DENSE), False),
    (op("matmul_kernel.3", LANE_DENSE), False),
    # the name alone, on an op that is no custom call
    (op("paged_attention.4", LANE_DENSE, target="kind=kLoop", opname="fusion"), False),
], ids=["block_minor", "lane_dense", "no_suffix", "unnamed_kernel", "another_kernel",
        "another_custom_call", "not_a_custom_call"])
def test_the_kernel_is_found_by_name(name, found):
    assert paged_attn_roofline.is_kernel(name) is found


def run_with(kernel_op):
    """A run whose traced window (0..1 s) holds two decode ticks, each with
    one call of ``kernel_op`` (2 ms) beside another custom call (5 ms)."""
    with open(os.path.join(CONFIGS, "smollm-360m-bika.json")) as f:
        cfg = json.load(f)
    ns = 1e9
    dev = plane("/device:TPU:0", XLA_Ops=[
        ev(kernel_op, 0.1 * ns, 0.002 * ns), ev(op("other.1", LANE_DENSE), 0.2 * ns, 0.005 * ns),
        ev(kernel_op, 0.5 * ns, 0.002 * ns)], XLA_Modules=[])
    host = plane("/host:CPU", python=[ev("window", 0, 1 * ns)])
    ticks = [NS(t0=0.1, t1=0.15, positions=[1000, 1500]), NS(t0=0.5, t1=0.55, positions=[1001])]
    rec = NS(ticks=ticks, prefills=[], trace_window=Window(0.0, 1.0))
    return NS(cfg=cfg, rec=rec, trace=trace_reduce.reduce_planes([host, dev]),
              device_kind="TPU v5 lite")


def test_the_roofline_reads_a_lane_dense_pool():
    run = run_with(op("paged_attention.2", LANE_DENSE))
    cfg = run.cfg
    hq, hkv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], model.head_dim(cfg)
    ops = bts = 0.0
    for pos in ([1000, 1500], [1001]):
        o, b = paged_attn.decode_call(pos, hq, hkv, d)
        ops, bts = ops + o, bts + b
    pk = peaks.peaks("TPU v5 lite")
    least = max(ops / pk["bf16_flops_per_s"], bts / pk["hbm_bytes_per_s"])
    assert paged_attn_roofline.read(run) == pytest.approx(100 * least / 0.004)


def test_the_roofline_is_silent_without_the_kernel():
    assert paged_attn_roofline.read(run_with(op("closed_call.2", BLOCK_MINOR))) is None
