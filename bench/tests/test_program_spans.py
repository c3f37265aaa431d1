"""Device idle time by the program's ``serve.*`` spans (``bench/program_spans.py``)
and the two metrics that read it, ``sched_idle_ms`` and ``dispatch_idle_ms``:
on the synthetic trace of ``test_trace_reduce.py`` with program spans added,
written as an xplane file where a reader needs one, and on the trace recorded
on a TPU v5e, which predates the spans."""
import os

import pytest

from bench import manifest, program_spans, trace_reduce
from bench.tests.test_trace_reduce import DATA, ev, synthetic

CELL = "smollm-360m-bika.chat"

# inside the synthetic window (100..1100): the harness's tick 100..600 holds
# prefill 120..320 and decode 420..590; device idle 150..200, 350..450 and
# 550..1100
PROGRAM = [ev("serve.tick", 105, 490), ev("serve.admit", 110, 220),
           ev("serve.prefill_chunk#rid=3,chunk=0#", 125, 185),
           ev("serve.upload", 425, 15), ev("serve.dispatch", 440, 20),
           ev("serve.token_pull", 460, 120), ev("serve.emit", 585, 9)]

# ns of idle by label: 150..200 in the chunk; 350..425 tick's self time,
# 425..440 upload, 440..450 dispatch; 550..580 token pull, 580..585 and
# 594..595 tick, 585..594 emit, 595..600 the harness's tick, 600..700 and
# 1000..1100 outside every span, 700..1000 waiting for an arrival
IDLE_NS = {"serve.prefill_chunk": 50, "serve.tick": 81, "serve.upload": 15,
           "serve.dispatch": 10, "serve.token_pull": 30, "serve.emit": 9, "tick": 5,
           "none": 200, "wait_arrival": 300}


def with_program_spans(n_chips=1):
    planes = synthetic(n_chips)
    planes[0].lines[0].events += PROGRAM
    return planes


@pytest.mark.parametrize("n_chips", [1, 2])
def test_program_spans_split_the_idle_time(n_chips):
    sp = program_spans.split(with_program_spans(n_chips))
    idle = sp.by_label()
    assert idle == pytest.approx({k: v * 1e-9 for k, v in IDLE_NS.items()})
    ((secs, at, by),) = sp.longest(1)
    assert (secs, at) == pytest.approx((550e-9, 450e-9))
    assert by == pytest.approx({k: v * 1e-9 for k, v in {
        "serve.token_pull": 30, "serve.tick": 6, "serve.emit": 9, "tick": 5, "none": 200,
        "wait_arrival": 300}.items()})
    r = trace_reduce.reduce_planes(synthetic(n_chips))
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)


@pytest.mark.parametrize("n_chips", [1, 2])
def test_the_reduction_ignores_program_spans(n_chips):
    """Busy time, idle share, op and module times, and the breakdown read the
    same with and without the program's spans."""
    plain = trace_reduce.reduce_planes(synthetic(n_chips))
    spans = trace_reduce.reduce_planes(with_program_spans(n_chips))
    assert (spans.window_s, spans.busy_s, spans.idle_share, spans.n_chips) == \
        (plain.window_s, plain.busy_s, plain.idle_share, plain.n_chips)
    assert spans.ops == plain.ops and spans.modules == plain.modules
    assert spans.breakdown == plain.breakdown


def test_a_trace_without_program_spans_reads_none():
    assert program_spans.split(synthetic()) is None
    from jax.profiler import ProfileData

    planes = ProfileData.from_file(os.path.join(DATA, "small.xplane.pb")).planes
    assert program_spans.split(planes) is None


def xspace_text(planes) -> str:
    """The planes as an XSpace text proto, for ``ProfileData``."""
    out = []
    for i, pl in enumerate(planes, 1):
        names = sorted({e.name for ln in pl.lines for e in ln.events})
        meta = {n: k for k, n in enumerate(names, 1)}
        lines = "".join(
            f'lines {{ id: {j} name: "{ln.name}" timestamp_ns: 0 '
            + "".join(f"events {{ metadata_id: {meta[e.name]} offset_ps: {int(e.start_ns) * 1000}"
                      f" duration_ps: {int(e.duration_ns) * 1000} }} " for e in ln.events)
            + "} " for j, ln in enumerate(pl.lines, 1))
        md = "".join(f'event_metadata {{ key: {k} value {{ id: {k} name: "{n}" }} }} '
                     for n, k in meta.items())
        out.append(f'planes {{ id: {i} name: "{pl.name}" {lines}{md}}}')
    return "\n".join(out)


@pytest.fixture
def traced_run(tmp_path, monkeypatch):
    """A run of ``CELL`` whose trace ``bench/run.py`` would have left at
    ``.bench_work/trace-<cell>``, as the readers find it."""
    from jax.profiler import ProfileData

    from bench import run as bench_run

    def make(planes):
        monkeypatch.setattr(bench_run, "WORK_DIR", str(tmp_path))
        d = tmp_path / f"trace-{CELL}" / "plugins" / "profile" / "1"
        d.mkdir(parents=True)
        path = d / "host.xplane.pb"
        path.write_bytes(ProfileData.text_proto_to_serialized_xspace(xspace_text(planes)))
        bench = manifest.load()
        wl = manifest.workload(bench, CELL)
        return bench_run.Context(cfg=manifest.config_file(bench, wl["config"]),
                                 traffic=manifest.traffic_file(wl["traffic"]),
                                 trace=trace_reduce.reduce_file(str(path)))

    return make


def test_the_readers_on_a_trace_with_program_spans(traced_run):
    run = traced_run(with_program_spans())
    # one jit_tick program in the synthetic window
    sched = IDLE_NS["serve.tick"] + IDLE_NS["serve.emit"]
    dispatch = (IDLE_NS["serve.upload"] + IDLE_NS["serve.dispatch"]
                + IDLE_NS["serve.token_pull"] + IDLE_NS["serve.prefill_chunk"])
    assert manifest.metric_reader("sched_idle_ms")(run) == pytest.approx(sched * 1e-6)
    assert manifest.metric_reader("dispatch_idle_ms")(run) == pytest.approx(dispatch * 1e-6)


@pytest.mark.parametrize("metric", ["sched_idle_ms", "dispatch_idle_ms"])
def test_the_readers_read_none_without_program_spans(traced_run, metric):
    assert manifest.metric_reader(metric)(traced_run(synthetic())) is None


@pytest.mark.parametrize("metric", ["sched_idle_ms", "dispatch_idle_ms"])
def test_the_readers_read_none_for_a_configuration_no_cell_runs(traced_run, metric):
    run = traced_run(with_program_spans())
    run.cfg = {**run.cfg, "num_hidden_layers": 2}
    assert manifest.metric_reader(metric)(run) is None


def test_the_metrics_spans_are_the_programs():
    """Each span the program opens in a tick feeds exactly one metric."""
    assert not set(program_spans.SCHED) & set(program_spans.DISPATCH)
    assert {e.name.partition("#")[0] for e in PROGRAM} <= \
        set(program_spans.SCHED) | set(program_spans.DISPATCH)
    assert len(program_spans.SCHED + program_spans.DISPATCH) == 10

