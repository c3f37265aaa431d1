"""Observability layer tests (repro.obs + serve wiring): fake-clock span
ordering, ring-buffer eviction, NullTracer no-ops, Perfetto export schema,
Prometheus exposition format, the serve loop's spans in a jax.profiler
trace and in the Tracer, span self times, the registry<->RunMetrics feed,
and exact trace<->metrics reconciliation on a real scheduler run (the
invariant benchmarks/trace_report.py --validate gates in CI)."""
import glob
import json

import jax
import numpy as np
import pytest

from repro.obs.profile import profile_trace
from repro.obs.registry import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
)
from repro.obs.trace import (
    NULL_TRACER,
    TRACE_SCHEMA_VERSION,
    Tracer,
    get_tracer,
    records_to_perfetto,
    self_times,
    set_tracer,
)
from repro.serve.metrics import RequestMetrics, RunMetrics


class FakeClock:
    """Deterministic clock: each call advances by ``step``."""

    def __init__(self, start: float = 100.0, step: float = 1.0):
        self.t = start
        self.step = step

    def __call__(self) -> float:
        t, self.t = self.t, self.t + self.step
        return t


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_tracer_fake_clock_ordering():
    clk = FakeClock()
    tr = Tracer(clock=clk)
    tr.event("submit", rid=0)                      # ts=100
    with tr.span("compile", kind="tick"):          # t0=101, t1=102
        pass
    tr.add_span("decode", "slot0", 103.0, 107.5, rid=0, n_tokens=4)
    recs = tr.records
    assert [r.name for r in recs] == ["submit", "compile", "decode"]
    assert recs[0].kind == "event" and recs[0].ts == 100.0 and recs[0].dur is None
    assert recs[0].args == {"rid": 0}
    assert recs[1].kind == "span" and recs[1].ts == 101.0 and recs[1].dur == 1.0
    assert recs[2].ts == 103.0 and recs[2].dur == 4.5
    assert recs[2].track == "slot0"
    # explicit-stamp spans clamp negative durations to 0
    tr.add_span("bad", "scheduler", 10.0, 9.0)
    assert tr.records[-1].dur == 0.0


def test_tracer_ring_eviction():
    tr = Tracer(clock=FakeClock(), capacity=4)
    for i in range(6):
        tr.event(f"e{i}")
    assert len(tr) == 4
    assert tr.dropped == 2
    assert [r.name for r in tr.records] == ["e2", "e3", "e4", "e5"]
    assert tr.header()["dropped"] == 2
    with pytest.raises(ValueError):
        Tracer(capacity=0)


def test_null_tracer_noop():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.event("x", rid=1) is None
    assert NULL_TRACER.add_span("x", "t", 0.0, 1.0) is None
    # the disabled span is the bare profiler annotation: it records nothing
    assert isinstance(NULL_TRACER.span("a", rid=1), jax.profiler.TraceAnnotation)
    with NULL_TRACER.span("x"):
        pass
    assert len(NULL_TRACER) == 0 and NULL_TRACER.records == []


def test_global_tracer_hook():
    assert get_tracer() is NULL_TRACER
    tr = Tracer(clock=FakeClock())
    prev = set_tracer(tr)
    try:
        assert prev is NULL_TRACER
        assert get_tracer() is tr
    finally:
        set_tracer(None)
    assert get_tracer() is NULL_TRACER


def test_write_jsonl_header_footer(tmp_path):
    tr = Tracer(clock=FakeClock())
    tr.event("submit", rid=0)
    tr.add_span("decode", "slot0", 1.0, 2.0, rid=0)
    path = tmp_path / "t.jsonl"
    tr.write_jsonl(str(path), summary={"goodput_tok_s": 5.0},
                   requests=[{"rid": 0, "ttft_s": 0.5}])
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines[0]["kind"] == "meta"
    assert lines[0]["schema_version"] == TRACE_SCHEMA_VERSION
    assert lines[0]["n_records"] == 2
    assert lines[1]["name"] == "submit" and "dur" not in lines[1]
    assert lines[2]["name"] == "decode" and lines[2]["dur"] == 1.0
    assert lines[3]["kind"] == "meta" and lines[3]["footer"]
    assert lines[3]["summary"]["goodput_tok_s"] == 5.0
    assert lines[3]["requests"][0]["rid"] == 0


def test_perfetto_golden_schema():
    tr = Tracer(clock=FakeClock())
    tr.add_span("prefill", "slot0", 10.0, 10.5, rid=1)
    tr.add_span("queued", "requests", 10.0, 11.0, async_id=1, rid=1)
    tr.event("prefix_hit", track="scheduler", rid=1)
    pf = tr.to_perfetto()
    assert pf["displayTimeUnit"] == "ms"
    assert pf["metadata"]["schema_version"] == TRACE_SCHEMA_VERSION
    evs = pf["traceEvents"]
    # one thread_name + thread_sort_index metadata pair per track
    meta = [e for e in evs if e["ph"] == "M"]
    names = {e["args"]["name"] for e in meta if e["name"] == "thread_name"}
    assert names == {"scheduler", "requests", "slot0"}
    # scheduler gets the lowest tid (sort priority), slots after
    tid_of = {e["args"]["name"]: e["tid"] for e in meta
              if e["name"] == "thread_name"}
    assert tid_of["scheduler"] < tid_of["slot0"]
    assert tid_of["requests"] < tid_of["slot0"]
    # complete span: X with dur in us, ts relative to the earliest record
    x = next(e for e in evs if e["ph"] == "X")
    assert x["name"] == "prefill" and x["dur"] == pytest.approx(0.5e6)
    assert x["ts"] == pytest.approx(0.0)
    assert x["args"]["rid"] == 1
    # instant event
    i = next(e for e in evs if e["ph"] == "i")
    assert i["name"] == "prefix_hit" and i["s"] == "t"
    # async pair: balanced b/e with matching cat/id
    b = next(e for e in evs if e["ph"] == "b")
    e_ = next(e for e in evs if e["ph"] == "e")
    assert b["id"] == e_["id"] == 1 and b["cat"] == e_["cat"] == "queued"
    assert e_["ts"] == pytest.approx(1e6)
    assert {e["ph"] for e in evs} <= {"M", "X", "i", "b", "e"}


def test_perfetto_accepts_plain_dicts():
    recs = [{"kind": "span", "name": "s", "track": "scheduler", "ts": 1.0,
             "dur": 0.25},
            {"kind": "meta", "schema_version": 1},  # skipped
            {"kind": "event", "name": "e", "track": "scheduler", "ts": 1.1}]
    pf = records_to_perfetto(recs)
    phs = [e["ph"] for e in pf["traceEvents"] if e["ph"] != "M"]
    assert phs == ["X", "i"]


# ---------------------------------------------------------------------------
# MetricsRegistry
# ---------------------------------------------------------------------------


def test_counter_semantics():
    c = Counter("reqs_total", "requests", ["mode"])
    c.inc(mode="bika")
    c.inc(2.0, mode="bika")
    c.inc(mode="bnn")
    assert c.value(mode="bika") == 3.0
    assert c.value(mode="bnn") == 1.0
    with pytest.raises(ValueError):
        c.inc(-1.0, mode="bika")  # counters only go up
    with pytest.raises(ValueError):
        c.inc(1.0)  # missing declared label
    with pytest.raises(ValueError):
        c.inc(1.0, mode="bika", extra="x")  # undeclared label


def test_gauge_last_write_wins():
    g = Gauge("occupancy", "", ["engine"])
    g.set(0.5, engine="paged")
    g.set(0.75, engine="paged")
    assert g.value(engine="paged") == 0.75
    g.inc(0.25, engine="paged")
    assert g.value(engine="paged") == 1.0


def test_histogram_cumulative_buckets():
    h = Histogram("lat", "", ["m"], buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 0.5, 5.0, 50.0):
        h.observe(v, m="x")
    assert h.count(m="x") == 5
    assert h.sum(m="x") == pytest.approx(56.05)
    lines = list(h.expose())
    # cumulative: le=0.1 -> 1, le=1 -> 3, le=10 -> 4, +Inf -> 5
    assert 'lat_bucket{m="x",le="0.1"} 1' in lines
    assert 'lat_bucket{m="x",le="1"} 3' in lines
    assert 'lat_bucket{m="x",le="10"} 4' in lines
    assert 'lat_bucket{m="x",le="+Inf"} 5' in lines
    assert 'lat_count{m="x"} 5' in lines
    snap = h.snapshot()[0]
    assert snap["buckets"]["+Inf"] == 5 and snap["count"] == 5


def test_registry_prometheus_exposition_format():
    reg = MetricsRegistry()
    reg.counter("serve_requests_total", "completed requests",
                ["mode"]).inc(3, mode="bika")
    reg.gauge("serve_run_goodput_tok_s", "goodput").set(12.5)
    text = reg.to_prometheus()
    lines = text.splitlines()
    assert "# HELP serve_requests_total completed requests" in lines
    assert "# TYPE serve_requests_total counter" in lines
    assert 'serve_requests_total{mode="bika"} 3' in lines
    assert "# TYPE serve_run_goodput_tok_s gauge" in lines
    assert "serve_run_goodput_tok_s 12.5" in lines
    # HELP/TYPE precede the samples of their metric
    assert lines.index("# TYPE serve_requests_total counter") \
        < lines.index('serve_requests_total{mode="bika"} 3')
    assert text.endswith("\n")


def test_registry_idempotent_getters_and_clashes():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "", ["a"])
    assert reg.counter("x_total", "", ["a"]) is c1
    with pytest.raises(ValueError):
        reg.gauge("x_total")  # kind clash
    with pytest.raises(ValueError):
        reg.counter("x_total", "", ["b"])  # label clash
    with pytest.raises(ValueError):
        reg.counter("bad name")
    with pytest.raises(ValueError):
        reg.counter("ok_total", "", ["bad-label"])
    assert "x_total" in reg and reg.get("nope") is None


def test_registry_snapshot_json_roundtrip(tmp_path):
    reg = MetricsRegistry()
    reg.histogram("h_seconds", "", ["e"]).observe(0.02, e="paged")
    path = tmp_path / "m.json"
    reg.write_json(str(path))
    snap = json.loads(path.read_text())
    assert snap["h_seconds"]["type"] == "histogram"
    assert snap["h_seconds"]["values"][0]["count"] == 1


# ---------------------------------------------------------------------------
# profiler annotations and span self times
# ---------------------------------------------------------------------------


def _host_events(log_dir):
    """(name, start_ns, end_ns) of every event on the /host:CPU plane of the
    one xplane under ``log_dir``."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(e.name, e.start_ns, e.start_ns + e.duration_ns)
            for pl in ProfileData.from_file(path).planes if pl.name == "/host:CPU"
            for ln in pl.lines for e in ln.events]


@pytest.mark.parametrize("tracer", [NULL_TRACER, "recording"])
def test_spans_land_in_the_profiler_trace(tmp_path, tracer):
    tr = Tracer(clock=FakeClock()) if tracer == "recording" else tracer
    with profile_trace(str(tmp_path)):
        with tr.span("serve.tick"):
            with tr.span("serve.upload", rows=2):
                pass
    ev = {n: (s, e) for n, s, e in _host_events(tmp_path)}
    assert ev["serve.tick"][0] <= ev["serve.upload"][0] <= ev["serve.upload"][1] \
        <= ev["serve.tick"][1]
    # profile_trace turns the Python tracer off: no per-call events
    assert not [n for n in ev if n.startswith("$")]
    if tr.enabled:
        assert [(r.name, r.track, r.ts, r.dur, r.args) for r in tr.records] == [
            ("serve.upload", "scheduler", 101.0, 1.0, {"rows": 2}),
            ("serve.tick", "scheduler", 100.0, 3.0, {})]


def test_self_times_subtract_child_spans():
    tr = Tracer(clock=FakeClock())
    tr.add_span("serve.tick", "scheduler", 0.0, 10.0)
    tr.add_span("serve.admit", "scheduler", 1.0, 4.0)
    tr.add_span("serve.kv_reserve", "scheduler", 1.5, 2.0)
    tr.add_span("serve.dispatch", "scheduler", 5.0, 7.0)
    tr.add_span("serve.tick", "scheduler", 10.0, 11.0)  # a sibling, not a child
    tr.add_span("decode", "slot0", 0.0, 20.0)  # not a serve.* span
    assert self_times(tr.records) == {"serve.tick": pytest.approx(6.0),
                                      "serve.admit": pytest.approx(2.5),
                                      "serve.kv_reserve": pytest.approx(0.5),
                                      "serve.dispatch": pytest.approx(2.0)}


def test_profile_trace_null_paths():
    with profile_trace(None):
        pass
    with profile_trace(""):
        pass


# ---------------------------------------------------------------------------
# RunMetrics <-> registry feed
# ---------------------------------------------------------------------------


def _finished_request(rid, t0, *, queue=1.0, prefill=0.5, n_tokens=5,
                      tpot=0.25):
    rm = RequestMetrics(rid=rid, prompt_len=4, t_submit=t0)
    rm.t_admit = t0 + queue
    rm.t_first_token = rm.t_admit + prefill
    rm.t_done = rm.t_first_token + tpot * (n_tokens - 1)
    rm.n_tokens = n_tokens
    return rm


def test_request_metrics_breakdown_fields():
    rm = _finished_request(0, 10.0)
    assert rm.queue_wait == pytest.approx(1.0)
    assert rm.prefill_latency == pytest.approx(0.5)
    assert rm.ttft == pytest.approx(1.5)
    assert rm.tpot == pytest.approx(0.25)
    d = rm.to_dict()
    assert d["queue_wait_s"] == pytest.approx(1.0)
    assert d["prefill_s"] == pytest.approx(0.5)
    # unstamped requests expose None, not garbage
    assert RequestMetrics(rid=1).to_dict()["queue_wait_s"] is None


def test_summary_tpot_percentiles_and_breakdown():
    run = RunMetrics(n_slots=2)
    for i, tpot in enumerate((0.1, 0.2, 0.3, 0.4, 10.0)):
        run.finish_request(_finished_request(i, float(i), tpot=tpot))
    s = run.summary()
    assert s["tpot_p50_s"] == pytest.approx(0.3)  # robust to the straggler
    assert s["tpot_p95_s"] == pytest.approx(10.0)
    assert s["tpot_mean_s"] == pytest.approx(2.2)
    assert s["queue_wait_mean_s"] == pytest.approx(1.0)
    assert s["prefill_p95_s"] == pytest.approx(0.5)
    assert "requests" not in s
    s2 = run.summary(include_requests=True)
    assert [r["rid"] for r in s2["requests"]] == [0, 1, 2, 3, 4]


def test_run_metrics_feeds_registry():
    reg = MetricsRegistry()
    run = RunMetrics(n_slots=2).bind_registry(reg, mode="bika", engine="paged",
                                              route="fused")
    lb = dict(mode="bika", engine="paged", route="fused")
    for i in range(3):
        run.finish_request(_finished_request(i, float(i)))
    assert reg.get("serve_requests_total").value(**lb) == 3
    assert reg.get("serve_tokens_total").value(**lb) == 15
    h = reg.get("serve_ttft_seconds")
    assert h.count(**lb) == 3
    assert h.sum(**lb) == pytest.approx(3 * 1.5)
    assert reg.get("serve_queue_wait_seconds").count(**lb) == 3
    # publish: summary scalars land as serve_run_* gauges, consistent with
    # the summary dict itself
    run.t_start, run.t_end = 0.0, 10.0
    run.publish()
    s = run.summary()
    for key in ("goodput_tok_s", "completed_requests", "tpot_p50_s"):
        assert reg.get(f"serve_run_{key}").value(**lb) == pytest.approx(s[key])
    # registry counters survive a window reset (Prometheus semantics): a new
    # bound window keeps accumulating into the same counters
    run2 = RunMetrics(n_slots=2).bind_registry(reg, **lb)
    run2.finish_request(_finished_request(9, 0.0))
    assert reg.get("serve_requests_total").value(**lb) == 4


def test_unbound_run_metrics_publish_is_noop():
    run = RunMetrics(n_slots=1)
    run.finish_request(_finished_request(0, 0.0))
    run.publish()  # no registry bound: must not raise


# ---------------------------------------------------------------------------
# scheduler integration: exact trace<->metrics reconciliation
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def lm():
    import jax

    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.nn.module import unbox

    cfg = get_smoke("smollm-360m", compute_mode="dense", remat=False)
    api = build_model(cfg, phase="train")
    params = unbox(api.init(jax.random.PRNGKey(7)))
    return cfg, api, params


def _lifecycle(tracer):
    per_rid = {}
    for r in tracer.records:
        rid = r.args.get("rid")
        if r.kind == "span" and rid is not None and \
                r.name in ("queued", "prefill", "decode"):
            per_rid.setdefault(rid, {})[r.name] = r
    return per_rid


@pytest.mark.parametrize("engine", ["continuous", "paged"])
def test_trace_reconciles_with_metrics_exactly(lm, engine):
    from repro.serve.engine import Request, ServeEngine

    cfg, api, params = lm
    tracer = Tracer()
    reg = MetricsRegistry()
    eng = ServeEngine(api, params, cfg, engine=engine, n_slots=2, max_len=32,
                      kv_block_size=8, prefill_chunk=8, tracer=tracer,
                      registry=reg)
    rng = np.random.RandomState(0)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, 4 + i).astype(np.int32),
                    max_new_tokens=4) for i in range(4)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 4
    spans = _lifecycle(tracer)
    assert sorted(spans) == [0, 1, 2, 3]
    for r in done:
        rm = r.metrics
        sp = spans[r.rid]
        # same clock stamps -> exact equality, not approximate
        assert sp["queued"].ts == rm.t_submit
        assert sp["queued"].dur == rm.queue_wait
        assert sp["prefill"].ts == rm.t_admit
        assert sp["prefill"].dur == rm.prefill_latency
        assert sp["queued"].dur + sp["prefill"].dur == pytest.approx(
            rm.ttft, abs=1e-12)
        assert sp["decode"].dur == rm.t_done - rm.t_first_token
        assert sp["prefill"].track == sp["decode"].track  # same slot
    # registry saw every completion with the engine's labels
    assert reg.get("serve_requests_total").value(
        mode="dense", engine=engine, route=cfg.paged_attn_route) == 4
    # paged point events present
    names = {r.name for r in tracer.records if r.kind == "event"}
    assert "submit" in names
    if engine == "paged":
        assert "prefix_miss" in names or "prefix_hit" in names


SERVE_SPANS = {"serve.tick", "serve.admit", "serve.first_token", "serve.upload",
               "serve.dispatch", "serve.token_pull", "serve.emit"}
PAGED_SPANS = {"serve.kv_reserve", "serve.prefill_chunk", "serve.kv_register"}


def _serve_tiny(lm, engine, tracer=None):
    from repro.serve.engine import Request, ServeEngine

    cfg, api, params = lm
    eng = ServeEngine(api, params, cfg, engine=engine, n_slots=2, max_len=32,
                      kv_block_size=8, prefill_chunk=8, tracer=tracer)
    rng = np.random.RandomState(2)
    for i in range(3):
        eng.submit(Request(rid=i, prompt=rng.randint(0, cfg.vocab, 9 + i).astype(np.int32),
                           max_new_tokens=3))
    assert len(eng.run()) == 3
    return eng


def _expected_spans(engine):
    return SERVE_SPANS | (PAGED_SPANS if engine == "paged" else set())


@pytest.mark.parametrize("engine", ["continuous", "paged"])
def test_serve_spans_in_the_profiler_trace(lm, engine, tmp_path):
    """With no Tracer recording, the serve loop's spans still reach a
    jax.profiler trace's host plane, nested as the tick runs them."""
    with profile_trace(str(tmp_path)):
        eng = _serve_tiny(lm, engine)
    serve = [(n, s, e) for n, s, e in _host_events(tmp_path) if n.startswith("serve.")]
    assert {n for n, _, _ in serve} == _expected_spans(engine)
    ticks = [(s, e) for n, s, e in serve if n == "serve.tick"]
    for n, s, e in serve:
        if n in ("serve.upload", "serve.dispatch", "serve.token_pull"):
            assert any(ts <= s and e <= te for ts, te in ticks), n
    pulls = sum(n == "serve.token_pull" for n, _, _ in serve)
    assert pulls == eng.metrics.decode_steps > 0


@pytest.mark.parametrize("engine", ["continuous", "paged"])
def test_tracer_records_serve_spans_on_the_scheduler_track(lm, engine):
    tracer = Tracer(clock=FakeClock())
    eng = _serve_tiny(lm, engine, tracer)
    serve = [r for r in tracer.records if r.name.startswith("serve.")]
    assert {r.name for r in serve} == _expected_spans(engine)
    assert {r.track for r in serve} == {"scheduler"}
    assert sum(r.name == "serve.token_pull" for r in serve) == eng.metrics.decode_steps
    own = self_times(tracer.records)
    assert set(own) == _expected_spans(engine) and min(own.values()) >= 0


def test_launcher_prints_serve_span_self_times(tmp_path, capsys):
    from repro.launch.serve import main

    out = tmp_path / "trace.jsonl"
    assert main(["--arch", "smollm-360m", "--smoke", "--engine", "paged",
                 "--requests", "3", "--new-tokens", "3", "--max-len", "32",
                 "--kv-block-size", "8", "--prefill-chunk", "8",
                 "--trace-out", str(out), "--profile-dir", str(tmp_path / "prof")]) == 0
    assert "[serve] profile (serve.* span self times): serve." in capsys.readouterr().out
    names = {json.loads(ln).get("name") for ln in out.read_text().splitlines()}
    assert SERVE_SPANS | PAGED_SPANS <= names
    host = {n for n, _, _ in _host_events(tmp_path / "prof")}
    assert SERVE_SPANS | PAGED_SPANS <= host
    assert not [n for n in host if n.startswith("$")]


def test_trace_report_validate_on_real_run(lm, tmp_path):
    """End-to-end: write the JSONL a serve run produces, then run
    benchmarks/trace_report.py validation on it."""
    import pathlib
    import sys

    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[1]))
    try:
        from benchmarks.trace_report import load, validate
    finally:
        sys.path.pop(0)

    from repro.serve.engine import Request, ServeEngine

    cfg, api, params = lm
    tracer = Tracer()
    eng = ServeEngine(api, params, cfg, engine="paged", n_slots=2, max_len=32,
                      kv_block_size=8, prefill_chunk=8, tracer=tracer)
    rng = np.random.RandomState(1)
    reqs = [Request(rid=i, prompt=rng.randint(0, cfg.vocab, 5).astype(np.int32),
                    max_new_tokens=3) for i in range(3)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    path = tmp_path / "trace.jsonl"
    tracer.write_jsonl(str(path), summary=eng.metrics.summary(),
                       requests=[r.metrics.to_dict() for r in done])
    data = load(str(path))
    assert data["header"]["schema_version"] == TRACE_SCHEMA_VERSION
    fails = validate(data, tol=1e-6)
    assert fails == [], fails
    # corrupt one span: reconciliation must catch it
    bad = dict(data["records"][0])
    for r in data["records"]:
        if r.get("name") == "queued":
            r["dur"] = r["dur"] + 1.0
            bad = r
            break
    fails = validate(data, tol=1e-6)
    assert fails, f"validation missed corrupted span {bad}"


def test_disabled_path_emits_nothing(lm):
    """Default construction (no tracer/registry) keeps the global
    NULL_TRACER silent."""
    from repro.serve.engine import Request, ServeEngine

    cfg, api, params = lm
    eng = ServeEngine(api, params, cfg, engine="continuous", n_slots=2,
                      max_len=32)
    assert eng.scheduler.tracer is NULL_TRACER
    eng.submit(Request(rid=0, prompt=np.arange(1, 5, dtype=np.int32),
                       max_new_tokens=2))
    done = eng.run()
    assert len(done) == 1
    assert len(NULL_TRACER) == 0
