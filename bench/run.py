#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload smollm-360m-bika.chat --seed 7 --seconds 40 --trace 0

Reads the cell from BENCHMARK.json, its configuration from
``bench/configs/<config>.json``, its traffic from
``bench/traffic/<traffic>.json`` and the limits of its correctness check
from ``bench/cells/<cell>.json``. Set-up makes the serve-form weights on the
device from the seed, builds the paged engine, and compiles and runs its two
programs (one prefill chunk, one decode tick) on a throwaway request, every
matmul at ``MATMUL_PRECISION``; the persistent compile cache
lives in the checkout (``.jax_cache``) unless ``JAX_COMPILATION_CACHE_DIR``
names another. Then the traffic runs open loop and the window is measured.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` traces the
first ``TRACE_SECONDS`` of the window with the JAX profiler and reports the
cell's per-layer metrics. After the window the engine is freed and the
plain reference checks a sample of what was served (``bench/check.py``).

``setup_s`` is the program's set-up: process start to traffic start, plus
the seconds of every compile after traffic start. Stderr splits it into
phases (``Phases``) and prints the traffic's warm-up or ramp apart.

The last line of stdout is one JSON object; the numbers compared, each
with its limit, are the last lines of stderr and the result's last key.
Without a TPU, or with fewer chips than the cell asks for, it exits with
code 3 and prints no result.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import manifest  # noqa: E402

WORK_DIR = os.path.join(ROOT, ".bench_work")  # traces; listed in .gitignore

# A traced run traces the first seconds of its window only: a trace of the
# whole window would be hundreds of MB and take minutes to read back.
TRACE_SECONDS = 8.0

# The program's float32 dots at full float32 (its default runs them as one
# bfloat16 pass, which drifts from the reference as far as bfloat16 does).
MATMUL_PRECISION = "highest"


class NoAccelerator(RuntimeError):
    pass


class Phases:
    """Host seconds of each set-up phase; ``mark(name)`` ends one. In order:
    ``imports`` (JAX among them), ``device`` (the TPU runtime's start),
    ``weights``, ``engine``, ``warm_up`` and the harness's ``traffic_gen``,
    which takes tens of milliseconds and so stays in ``setup_s``."""

    def __init__(self, t0: float):
        self.t, self.secs = t0, {}

    def mark(self, name: str) -> None:
        now = time.perf_counter()
        self.secs[name] = now - self.t
        self.t = now


def setup_seconds(t_process: float, rec) -> float:
    """The program's set-up: process start to traffic start, plus every
    compile after traffic start (``rec`` is the window's ``RunRecord``)."""
    return rec.t_traffic - t_process + rec.compile_s


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def device_info(chips: int, require_tpu: bool) -> dict:
    import jax

    devs = jax.devices()
    if require_tpu and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoAccelerator(f"the cell needs {chips} TPU chip(s); JAX sees "
                            f"{len(devs)} {devs[0].platform} device(s)")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind, "count": chips}


def setup_jax() -> None:
    """Before JAX starts: the TPU runtime's logs go into the checkout (it logs
    under /tmp unless told otherwise), and the persistent compile cache is on."""
    if "TPU_LOG_DIR" not in os.environ:
        os.makedirs(os.path.join(WORK_DIR, "tpu_logs"), exist_ok=True)
        os.environ["TPU_LOG_DIR"] = os.path.join(WORK_DIR, "tpu_logs")
    import jax

    from repro.launch.cache import use_persistent_compile_cache

    use_persistent_compile_cache()
    # cache every program, however quick to compile, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             bench: dict | None = None, cfg: dict | None = None,
             require_tpu: bool = True, overrides: dict | None = None,
             traffic: dict | None = None, limits: dict | None = None,
             t_process: float | None = None, engine_hook=None, verify: bool = True) -> dict:
    """One run of one cell; returns the result object. ``cfg``,
    ``traffic``, ``limits`` and ``overrides`` (ArchConfig fields) let tests
    and the calibration tools swap the configuration; ``engine_hook(engine)``
    lets a test break the timed path underneath."""
    import jax

    t0 = T_PROCESS if t_process is None else t_process
    bench = bench or manifest.load()
    wl = manifest.workload(bench, cell)
    cfg = cfg or manifest.config_file(bench, wl["config"])
    traffic = traffic or manifest.traffic_file(wl["traffic"])
    limits = limits or manifest.cell_file(cell)["limits"]
    phases = Phases(t0)
    phases.mark("imports")
    dev = device_info(wl["chips"], require_tpu)
    phases.mark("device")
    with jax.default_matmul_precision(MATMUL_PRECISION):
        return _serve_and_check(cell, seed, seconds, trace, bench, cfg, traffic, limits, dev,
                                overrides, t0, phases, engine_hook, verify)


def _serve_and_check(cell, seed, seconds, trace, bench, cfg, traffic, limits, dev, overrides,
                     t0, phases, engine_hook, verify):
    import jax

    from bench import check, model, stats, traffic_gen
    from bench.serve_window import Driver

    eng = model.build_engine(cfg, seed, overrides=overrides, mark=phases.mark)
    if engine_hook is not None:
        engine_hook(eng)
    drv = Driver(eng)
    sv = cfg["serve"]
    drv.warm_up(prompt_len=sv["prefill_chunk"] + 1, new_tokens=2, vocab=cfg["vocab_size"])
    phases.mark("warm_up")
    items = traffic_gen.generate(traffic, seed, cfg["vocab_size"],
                                 traffic_gen.requests_needed(traffic, seconds))
    phases.mark("traffic_gen")
    trace_dir = os.path.join(WORK_DIR, f"trace-{cell}")
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
    hooks = {}
    if trace:
        spans = []

        def start():
            jax.profiler.start_trace(trace_dir)
            spans.append(jax.profiler.TraceAnnotation("window"))  # made while tracing
            spans[0].__enter__()

        def stop():
            spans[0].__exit__(None, None, None)
            jax.profiler.stop_trace()

        hooks = dict(trace_start=start, trace_stop=stop,
                     trace_s=min(seconds, TRACE_SECONDS))
    rec = drv.run(items, seconds, traffic["window"],
                  min_check_tokens=cfg["check"]["min_served_tokens"], **hooks)
    setup_s = setup_seconds(t0, rec)
    device = jax.devices()[0]
    dev["memory_peak_bytes"] = int((device.memory_stats() or {}).get("peak_bytes_in_use", 0))
    w = rec.window
    e2e = stats.end_to_end(list(rec.requests.values()), w, rec.t_stop)
    due = stats.due_in_window(list(rec.requests.values()), w)
    attempted = len(due)
    # a cell that drains counts a request still without a first token as
    # failed; one that runs above capacity leaves its backlog queued
    failed = (sum(1 for r in due if not r.token_times)
              if traffic["window"].get("drain") == "first_tokens" else 0)
    backlog = sum(1 for r in rec.requests.values()
                  if r.due < w.close and (r.t_admit is None or r.t_admit > w.close))
    log(f"window: {w.seconds:.3f} s, ramp {rec.ramp_s:.3f} s, drain {rec.drain_s:.3f} s, "
        f"then {rec.check_wait_s:.3f} s for requests to finish, "
        f"{attempted} requests due, {failed} without a first token, {backlog} due and "
        f"not admitted at the close, "
        f"{rec.compiles_in_window} compiles in the window, generator late p95 "
        f"{rec.late_submit_p95_s * 1e3:.3f} ms, {len(rec.outputs)} requests finished")
    log("set-up phases (s): " + ", ".join(f"{k} {v}" for k, v in phases.secs.items())
        + f", to traffic start {rec.t_traffic - t0}, compiles after traffic start "
        f"{rec.compiles_in_traffic} ({rec.compile_s} s); setup_s {setup_s}")
    if drv.saturated_at is not None:
        log(f"ramp_s: {rec.ramp_s} (saturated, with {drv.saturated_at[0]}/{sv['n_slots']} "
            f"slots busy and {drv.saturated_at[1]}/{drv.sched.kv.n_blocks} KV blocks in use)")
    else:
        log(f"ramp_s: {rec.ramp_s} (warm-up of {traffic['window']['warm_s']} s)")
    log(f"program counters over the window: {json.dumps(rec.counters)}")
    prompts = {it.rid: it.prompt for it in items}
    asked = {it.rid: it.max_new_tokens for it in items}
    outputs = dict(rec.outputs)
    must = []
    if traffic.get("prefix", {}).get("kind") == "system_prompts":
        must = check.required_sample({r: rec.requests[r] for r in outputs})
    metrics = {}
    reduced = None
    if trace:
        from bench import trace_reduce

        reduced = trace_reduce.reduce_dir(trace_dir)
        dev["busy_s"] = reduced.busy_s
        dev["window_s"] = reduced.window_s
        ctx = Context(rec=rec, cfg=cfg, traffic=traffic, trace=reduced,
                      device_kind=dev["kind"], n_slots=sv["n_slots"])
        for pm in manifest.metrics_for(bench, cell, "per_layer"):
            v = manifest.metric_reader(pm["name"])(ctx)
            if v is not None:
                metrics[pm["name"]] = {"value": v, "unit": pm["unit"]}
    else:
        for em in manifest.metrics_for(bench, cell, "end_to_end"):
            v = setup_s if em["name"] == "setup_s" else e2e.get(em["name"])
            if v is None:
                raise RuntimeError(f"{cell}: no value for {em['name']}")
            metrics[em["name"]] = {"value": v, "unit": em["unit"]}
    waits = stats.queue_waits(list(rec.requests.values()), w)
    log(f"end to end (host clock): setup_s={setup_s} {json.dumps(e2e)}, queue wait p95 "
        f"{stats.percentile(waits, 0.95) if waits else None} s (due time to slot claimed)")
    gaps = stats.itl_gaps(list(rec.requests.values()), w)
    if gaps:
        log(f"inter-token gaps: {len(gaps)}, ms at p50/p90/p93/p95/p97/p99: " + " / ".join(
            f"{1e3 * stats.percentile(gaps, q):.3f}"
            for q in (0.5, 0.9, 0.93, 0.95, 0.97, 0.99)))
    # free the program's state before the reference runs
    del eng, drv
    gc.collect()
    t_check = time.perf_counter()
    if not verify:  # calibration sweeps only: never a benchmark run
        return {"metrics": metrics, "device": dev, "attempted": attempted, "failed": failed}
    result = check.compare(cfg, seed, prompts, outputs, asked, limits, must=must)
    log(f"reference check: {time.perf_counter() - t_check:.3f} s over "
        f"{len(result['compared_requests'])} requests, {result['compared_tokens']} served "
        f"tokens, argmax agrees on {result['argmax_agree']}, not compared here: "
        f"{json.dumps(result['printed'])}")
    out = {"correct": result["correct"], "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown
    out["check_detail"] = {**{k: result[k] for k in ("compared_tokens", "argmax_agree")},
                           **result["printed"]}
    out["check"] = result["numbers"]
    return out


class Context:
    """What a per-layer metric's reader gets: the run's records, its
    configuration and traffic, the reduced trace and the device kind."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    setup_jax()
    try:
        out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        log(f"no accelerator: {e}")
        return 3
    from bench import check

    for line in check.describe({"numbers": out["check"]}):
        log(line)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
