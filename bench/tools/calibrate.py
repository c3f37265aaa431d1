#!/usr/bin/env python3
"""One-process calibration runs on the chip; never part of a benchmark run.

    python3 bench/tools/calibrate.py sweep --workload W --rates 1,2,3 --seconds 20
        the knee: the cell's traffic at each offered rate, unchecked;
    python3 bench/tools/calibrate.py readings --workload W --seeds 1,2,3 --seconds 20 [--control]
        every number of the correctness check, over many seeds in one
        process (``--control``: the program's own bfloat16 path);
    python3 bench/tools/calibrate.py traced --workload W --seconds 10
        one traced run, as ``bench/run.py --trace 1`` would make it;
    python3 bench/tools/calibrate.py tracedump --dir D
        the planes, lines and busiest event names of a profiler trace.

``--layers``, ``--rate`` and ``--warm`` override the cell's configuration
and traffic. Each run prints one JSON line to stdout.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from collections import Counter

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import manifest, run  # noqa: E402

CONTROL = {"compute_dtype": "bfloat16"}


def traffic(args, rate=None):
    """The cell's traffic, with ``--rate`` and ``--warm`` applied."""
    t = manifest.traffic_file(manifest.workload(manifest.load(), args.workload)["traffic"])
    t = dict(t, window=dict(t["window"]))
    if rate or args.rate:
        t["rate_per_s"] = rate or args.rate
    if args.warm is not None:  # a fixed warm-up, also for a cell that waits to saturate
        t["window"].update(opens="after_warm", warm_s=args.warm,
                           drain=t["window"].get("drain", "none"))
    return t


def config(args):
    """The cell's configuration, with ``--layers`` applied."""
    bench = manifest.load()
    cfg = dict(manifest.config_file(bench, manifest.workload(bench, args.workload)["config"]))
    if args.layers:
        cfg["num_hidden_layers"] = args.layers
    return cfg


def sweep(args):
    for rate in (float(r) for r in args.rates.split(",")):
        out = run.run_cell(args.workload, args.seed, args.seconds, False, cfg=config(args),
                           traffic=traffic(args, rate), verify=False,
                           t_process=time.perf_counter())
        print(json.dumps({"rate_per_s": rate, **out}), flush=True)


def readings(args):
    """The sound program, and with ``--control`` its bfloat16 path, on each
    seed (a control that crashes is recorded as such)."""
    base = config(args)
    runs = [] if args.control_only else [("sound", base, None)]
    if args.control:
        runs.append(("control_bfloat16", base, CONTROL))
    for seed in (int(s) for s in args.seeds.split(",")):
        for label, cfg, over in runs:
            try:
                out = run.run_cell(args.workload, seed, args.seconds, False, cfg=cfg,
                                   traffic=traffic(args), overrides=over,
                                   t_process=time.perf_counter())
            except Exception as e:  # a control that crashes has failed: record it
                print(json.dumps({"seed": seed, "run": label, "crashed": repr(e)[:300]}),
                      flush=True)
                continue
            print(json.dumps({"seed": seed, "run": label, "layers": cfg["num_hidden_layers"],
                              "correct": out["correct"], "check": out["check"],
                              "detail": out.get("check_detail"),
                              "metrics": out["metrics"]}), flush=True)


def traced(args):
    """One traced run of the cell (as ``bench/run.py --trace 1``)."""
    out = run.run_cell(args.workload, args.seed, args.seconds, True, cfg=config(args),
                       traffic=traffic(args), t_process=time.perf_counter())
    print(json.dumps(out), flush=True)


def tracedump(args):
    from jax.profiler import ProfileData

    from bench import trace_reduce

    pd = ProfileData.from_file(trace_reduce.find_xplane(args.dir))
    for pl in pd.planes:
        print("plane", pl.name)
        for ln in pl.lines:
            evs = list(ln.events)
            if not evs:
                continue
            names = Counter(e.name for e in evs)
            print(f"  line {ln.name!r}: {len(evs)} events, first {evs[0].start_ns}, "
                  f"last end {evs[-1].start_ns + evs[-1].duration_ns}")
            for name, n in names.most_common(args.top):
                print(f"    {n} x {name[:160]}")
            for pat in args.grep.split(","):
                hits = [e for e in evs if pat in e.name]
                for e in hits[:2]:
                    print(f"    [{pat}] {len(hits)} x {e.name[:300]} dur {e.duration_ns} "
                          f"stats {[(k, str(v)[:120]) for k, v in e.stats][:12]}")


def main():
    ap = argparse.ArgumentParser()
    sub = ap.add_subparsers(dest="cmd", required=True)
    s = sub.add_parser("sweep")
    s.add_argument("--workload", required=True)
    s.add_argument("--rates", required=True)
    s.add_argument("--seconds", type=float, default=20)
    s.add_argument("--seed", type=int, default=1)
    r = sub.add_parser("readings")
    r.add_argument("--workload", required=True)
    r.add_argument("--seeds", required=True)
    r.add_argument("--seconds", type=float, default=20)
    r.add_argument("--control", action="store_true")
    r.add_argument("--control-only", action="store_true")
    tr = sub.add_parser("traced")
    tr.add_argument("--workload", required=True)
    tr.add_argument("--seed", type=int, default=1)
    tr.add_argument("--seconds", type=float, default=10)
    for p in (s, r, tr):
        p.add_argument("--rate", type=float)
        p.add_argument("--warm", type=float)
        p.add_argument("--layers", type=int)
    t = sub.add_parser("tracedump")
    t.add_argument("--dir", required=True)
    t.add_argument("--top", type=int, default=12)
    t.add_argument("--grep", default="tpu_custom_call,paged,jit_tick,window,prefill,decode")
    args = ap.parse_args()
    if args.cmd != "tracedump":
        run.setup_jax()
    {"sweep": sweep, "readings": readings, "traced": traced,
     "tracedump": tracedump}[args.cmd](args)


if __name__ == "__main__":
    main()
