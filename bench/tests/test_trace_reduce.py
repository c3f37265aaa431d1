"""The trace reduction: on a synthetic trace whose answers are known, and on
a small trace recorded on a TPU v5e (``data/small.xplane.pb``: one second
of ``smollm-360m-bika.chat``)."""
import os
from types import SimpleNamespace as NS

import pytest

from bench import trace_reduce
from bench.metrics import paged_attn_roofline

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(name, start, dur):
    return NS(name=name, start_ns=start, duration_ns=dur)


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def synthetic(n_chips=1):
    host = plane("/host:CPU", python=[
        ev("window", 100, 1000),
        ev("tick", 100, 500), ev("prefill", 120, 200), ev("decode", 420, 170),
        ev("wait_arrival", 700, 300),
        ev("unrelated", 0, 5000)])
    dev = [plane(f"/device:TPU:{i}",
                 XLA_Ops=[ev("fusion.1", 50, 100),          # clipped to 100..150
                          ev("paged_attn_kernel", 200, 100),
                          ev("fusion.2", 250, 100),          # overlaps: busy to 350
                          ev("paged_attn_kernel", 450, 100),
                          ev("fusion.3", 1200, 50)],         # outside the window
                 XLA_Modules=[ev("jit_run(123)", 140, 220), ev("jit_tick(9)", 440, 120)])
           for i in range(n_chips)]
    return [host] + dev


@pytest.mark.parametrize("n_chips", [1, 2])
def test_busy_idle_kernel_and_module_times(n_chips):
    r = trace_reduce.reduce_planes(synthetic(n_chips))
    assert r.window_s == pytest.approx(1000e-9)
    # busy: 100..150, 200..350, 450..550 -> 300 ns of 1000
    assert r.busy_s == pytest.approx(300e-9)
    assert r.idle_share == pytest.approx(0.7)
    assert r.kernel_s(lambda n: "paged_attn" in n) == (pytest.approx(200e-9 * n_chips),
                                                      2 * n_chips)
    assert r.module_s("jit_tick") == (pytest.approx(120e-9 * n_chips), n_chips)
    assert r.n_chips == n_chips


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    r = trace_reduce.reduce_planes(synthetic())
    idle = dict(r.breakdown["idle_gaps"])
    # gaps: 150..200 (prefill), 350..450 (tick, between prefill and decode),
    # 550..1100 split by midpoint 825 -> wait_arrival
    assert idle["prefill"] == pytest.approx(50e-9)
    assert idle["tick"] == pytest.approx(100e-9)
    assert idle["wait_arrival"] == pytest.approx(550e-9)
    ops = dict(r.breakdown["device_ops"])
    assert ops["paged_attn_kernel paged_attn_kernel"] == pytest.approx(200e-9)
    assert len(r.breakdown["device_ops"]) <= 10


@pytest.mark.parametrize("name,op", [
    ("%fusion.3 = f32[8,128]{1,0:T(8,128)} fusion(f32[8,128]{1,0} %p), kind=kLoop", "fusion"),
    ("%copy-start.2 = (f32[1,32]{1,0}, f32[1,32]{1,0}, u32[]{:S(2)}) copy-start(f32[1,32] %x)",
     "copy-start"),
    ("%while.5 = (s32[], f32[32,960]{1,0}) while((s32[], f32[32,960]{1,0}) %t), body=%b",
     "while"),
])
def test_opcode_of_an_hlo_op(name, op):
    assert trace_reduce.opcode(name) == op


def test_a_trace_without_its_window_is_refused():
    planes = synthetic()
    planes[0].lines[0].events = [e for e in planes[0].lines[0].events if e.name != "window"]
    with pytest.raises(ValueError):
        trace_reduce.reduce_planes(planes)


def test_a_trace_recorded_on_the_chip():
    """0.12 s of smollm-360m-bika.chat (one layer) on a TPU v5 lite; its run
    reported decode_tick_ms 7.13875690909091, busy_s 0.095753539 and
    window_s 0.126322427 from this file. The trace predates the kernel's name
    (its paged-attention op is ``%closed_call.2``), so the kernel-time check
    below picks the op by the KV pool's shape as a test of the reduction; it
    no longer mirrors ``paged_attn_roofline``, which finds the kernel by name
    and reads nothing here."""
    r = trace_reduce.reduce_file(os.path.join(DATA, "small.xplane.pb"))
    assert r.n_chips == 1
    assert r.window_s == pytest.approx(0.126322427)
    assert r.busy_s == pytest.approx(0.095753539)
    secs, n = r.module_s("jit_tick")
    assert n >= 1 and 1e3 * secs / n == pytest.approx(7.13875690909091)
    secs, n = r.kernel_s(lambda name: 'custom_call_target="tpu_custom_call"' in name
                         and ",16,5,64]" in name)
    assert n > 0 and 0 < secs < r.busy_s
    assert r.kernel_s(paged_attn_roofline.is_kernel) == (0.0, 0)
    assert 0 < len(r.breakdown["device_ops"]) <= 10
    assert {k for k, _ in r.breakdown["idle_gaps"]} <= set(trace_reduce.HOST_SPANS) | {"none"}
    assert sum(v for _, v in r.breakdown["idle_gaps"]) == pytest.approx(r.window_s - r.busy_s)
