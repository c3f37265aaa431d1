"""Trace reader for launch.serve --trace-out JSONL files: per-phase and
per-request breakdowns, plus a ``--validate`` mode CI runs on the smoke
trace (.github/workflows/ci.yml).

    PYTHONPATH=src:. python benchmarks/trace_report.py /tmp/trace.jsonl
    PYTHONPATH=src:. python benchmarks/trace_report.py /tmp/trace.jsonl --validate

``--validate`` asserts the trace is self-consistent, not just well-formed:

- schema: header meta with the expected ``schema_version``; every record a
  span (``dur >= 0``) or event with name/track/ts; per-request lifecycle
  ordering (``queued`` ends where ``prefill`` starts, ``decode`` after).
- reconciliation: the scheduler stamps trace spans and RequestMetrics with
  the SAME clock reads, so for every request in the footer dump,
  ``queued.dur + prefill.dur == ttft_s`` and ``decode.dur / (n_tokens - 1)
  == tpot_s`` to within ``--tol`` (default 1us — fp round-trip through
  JSON, not clock skew). A ring-buffer-truncated trace (``dropped > 0``)
  only validates the requests whose spans survived.
- Perfetto export: ``records_to_perfetto`` of the records must produce
  paired async b/e events and only known phase types (the same JSON
  ``--perfetto-out`` writes, loadable at ui.perfetto.dev).
"""
from __future__ import annotations

import argparse
import json
import sys
from collections import defaultdict
from typing import Dict, List, Optional

try:
    from repro.obs.trace import TRACE_SCHEMA_VERSION, records_to_perfetto
except ImportError:  # standalone use without PYTHONPATH=src
    TRACE_SCHEMA_VERSION = 1
    records_to_perfetto = None

LIFECYCLE = ("queued", "prefill", "decode")


def load(path: str) -> Dict:
    """Parse a trace JSONL into {header, records, summary, requests}."""
    header: Optional[Dict] = None
    summary: Optional[Dict] = None
    requests: Optional[List[Dict]] = None
    records: List[Dict] = []
    with open(path) as fh:
        for ln, line in enumerate(fh, 1):
            line = line.strip()
            if not line:
                continue
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                raise SystemExit(f"{path}:{ln}: not JSON ({e})")
            kind = obj.get("kind")
            if kind == "meta":
                if obj.get("footer"):
                    summary = obj.get("summary")
                    requests = obj.get("requests")
                else:
                    header = obj
            else:
                records.append(obj)
    return {"header": header, "records": records, "summary": summary,
            "requests": requests}


def lifecycle_spans(records: List[Dict]) -> Dict[int, Dict[str, Dict]]:
    """rid -> {queued/prefill/decode/request: span record} for every request
    whose spans survived the ring buffer."""
    per_rid: Dict[int, Dict[str, Dict]] = defaultdict(dict)
    for r in records:
        if r.get("kind") != "span":
            continue
        name = r.get("name")
        rid = r.get("args", {}).get("rid", r.get("async_id"))
        if rid is None or name not in LIFECYCLE + ("request",):
            continue
        per_rid[int(rid)][name] = r
    return per_rid


def report(data: Dict) -> None:
    header = data["header"] or {}
    records = data["records"]
    spans = [r for r in records if r.get("kind") == "span"]
    events = [r for r in records if r.get("kind") == "event"]
    print(f"trace: {len(records)} records ({len(spans)} spans, "
          f"{len(events)} events), schema v{header.get('schema_version', '?')}, "
          f"{header.get('dropped', 0)} dropped")

    by_name: Dict[str, List[float]] = defaultdict(list)
    for s in spans:
        track = s.get("track", "")
        fam = "slots" if track.startswith("slot") else track
        # the same name on different track families stays distinct
        by_name[f"{s['name']} [{fam}]"].append(float(s.get("dur", 0.0)))
    if by_name:
        print("\nper-phase span totals:")
        for name in sorted(by_name, key=lambda n: -sum(by_name[n])):
            durs = by_name[name]
            print(f"  {name:<24} n={len(durs):<5} total={sum(durs):.4f}s "
                  f"mean={sum(durs) / len(durs):.5f}s")
    counts: Dict[str, int] = defaultdict(int)
    for e in events:
        counts[e["name"]] += 1
    if counts:
        print("\nevent counts:")
        for name in sorted(counts):
            print(f"  {name:<20} {counts[name]}")

    per_rid = lifecycle_spans(records)
    complete = {rid: sp for rid, sp in per_rid.items()
                if all(k in sp for k in LIFECYCLE)}
    if complete:
        print(f"\nper-request breakdown ({len(complete)} complete of "
              f"{len(per_rid)} seen):")
        print(f"  {'rid':>5} {'queue_wait':>11} {'prefill':>9} {'decode':>9} "
              f"{'ttft':>9} {'n_tok':>6}")
        for rid in sorted(complete):
            sp = complete[rid]
            q, p, d = (float(sp[k]["dur"]) for k in LIFECYCLE)
            ntok = sp["decode"].get("args", {}).get("n_tokens", "?")
            print(f"  {rid:>5} {q:>10.5f}s {p:>8.5f}s {d:>8.5f}s "
                  f"{q + p:>8.5f}s {ntok:>6}")
    if data["summary"]:
        s = data["summary"]
        print(f"\nfooter summary: {s.get('completed_requests')} requests, "
              f"goodput={s.get('goodput_tok_s', 0):.1f} tok/s, "
              f"ttft_mean={s.get('ttft_mean_s')}, "
              f"tpot_p50={s.get('tpot_p50_s')}")


def validate(data: Dict, *, tol: float) -> List[str]:
    """Schema + trace<->metrics reconciliation checks; returns failures."""
    fails: List[str] = []
    header, records = data["header"], data["records"]
    if header is None:
        return ["missing meta header line"]
    if header.get("schema_version") != TRACE_SCHEMA_VERSION:
        fails.append(f"schema_version {header.get('schema_version')} != "
                     f"{TRACE_SCHEMA_VERSION}")
    for i, r in enumerate(records):
        where = f"record {i} ({r.get('name')!r})"
        if r.get("kind") not in ("span", "event"):
            fails.append(f"{where}: kind {r.get('kind')!r}")
            continue
        if not isinstance(r.get("name"), str) or not isinstance(r.get("track"), str):
            fails.append(f"{where}: name/track must be strings")
        if not isinstance(r.get("ts"), (int, float)):
            fails.append(f"{where}: non-numeric ts")
        if r["kind"] == "span" and not (isinstance(r.get("dur"), (int, float))
                                        and r["dur"] >= 0):
            fails.append(f"{where}: span needs dur >= 0, got {r.get('dur')!r}")
        if r["kind"] == "event" and "dur" in r:
            fails.append(f"{where}: event carries a dur")

    per_rid = lifecycle_spans(records)
    dropped = int(header.get("dropped", 0) or 0)
    for rid, sp in sorted(per_rid.items()):
        if not all(k in sp for k in LIFECYCLE):
            if dropped == 0:
                missing = [k for k in LIFECYCLE if k not in sp]
                fails.append(f"rid {rid}: missing {missing} spans "
                             f"(nothing was dropped)")
            continue
        q, p, d = sp["queued"], sp["prefill"], sp["decode"]
        # same clock stamps: queued ends exactly where prefill starts, and
        # decode starts exactly at first-token time (= prefill end)
        if abs((q["ts"] + q["dur"]) - p["ts"]) > tol:
            fails.append(f"rid {rid}: queued end {q['ts'] + q['dur']} != "
                         f"prefill start {p['ts']}")
        if abs((p["ts"] + p["dur"]) - d["ts"]) > tol:
            fails.append(f"rid {rid}: prefill end != decode start")

    # reconcile against the footer's per-request RunMetrics dump
    reqs = data["requests"] or []
    n_checked = 0
    for rm in reqs:
        rid = rm.get("rid")
        sp = per_rid.get(rid, {})
        if not all(k in sp for k in LIFECYCLE):
            continue
        n_checked += 1
        ttft = rm.get("ttft_s")
        if ttft is not None:
            got = sp["queued"]["dur"] + sp["prefill"]["dur"]
            if abs(got - ttft) > tol:
                fails.append(f"rid {rid}: span ttft {got} != metrics {ttft}")
        qw = rm.get("queue_wait_s")
        if qw is not None and abs(sp["queued"]["dur"] - qw) > tol:
            fails.append(f"rid {rid}: queued span {sp['queued']['dur']} != "
                         f"queue_wait_s {qw}")
        pf = rm.get("prefill_s")
        if pf is not None and abs(sp["prefill"]["dur"] - pf) > tol:
            fails.append(f"rid {rid}: prefill span != prefill_s {pf}")
        tpot, ntok = rm.get("tpot_s"), rm.get("n_tokens", 0)
        if tpot is not None and ntok > 1:
            got = sp["decode"]["dur"] / (ntok - 1)
            if abs(got - tpot) > tol:
                fails.append(f"rid {rid}: span tpot {got} != metrics {tpot}")
    if reqs and n_checked == 0 and dropped == 0:
        fails.append("footer has requests but no lifecycle spans reconciled")

    if records_to_perfetto is not None and records:
        pf = records_to_perfetto(records)
        evs = pf.get("traceEvents", [])
        if not evs:
            fails.append("perfetto export produced no events")
        opens: Dict[tuple, int] = defaultdict(int)
        for e in evs:
            if e.get("ph") not in ("X", "i", "b", "e", "M"):
                fails.append(f"perfetto: unknown phase {e.get('ph')!r}")
            if e.get("ph") == "b":
                opens[(e.get("cat"), e.get("id"))] += 1
            elif e.get("ph") == "e":
                opens[(e.get("cat"), e.get("id"))] -= 1
        bad = {k: v for k, v in opens.items() if v != 0}
        if bad:
            fails.append(f"perfetto: unbalanced async b/e pairs: {bad}")
    return fails


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("trace_jsonl")
    ap.add_argument("--validate", action="store_true",
                    help="exit 1 unless schema + metrics reconciliation hold")
    ap.add_argument("--tol", type=float, default=1e-6,
                    help="reconciliation tolerance in seconds (JSON fp "
                         "round-trip, not clock skew)")
    ap.add_argument("--perfetto-out", default="",
                    help="also write Chrome trace_event JSON here")
    args = ap.parse_args(argv)
    data = load(args.trace_jsonl)
    report(data)
    if args.perfetto_out:
        if records_to_perfetto is None:
            raise SystemExit("--perfetto-out needs repro.obs on PYTHONPATH")
        with open(args.perfetto_out, "w") as fh:
            json.dump(records_to_perfetto(data["records"]), fh, sort_keys=True)
        print(f"perfetto -> {args.perfetto_out}")
    if args.validate:
        fails = validate(data, tol=args.tol)
        for f in fails:
            print(f"TRACE INVALID: {f}", file=sys.stderr)
        print("trace validation:", "FAIL" if fails else "PASS")
        return 1 if fails else 0
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
