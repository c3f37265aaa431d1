"""BENCHMARK.json: loading, validation, and finding each entry's files.

Everything belonging to one configuration, traffic mix or per-layer metric
is a file named after it, so a new cell needs only new files and new
entries here:

- ``bench/configs/<config>.json``  the configuration as it is run;
- ``bench/traffic/<traffic>.json``  the traffic mix's parameters;
- ``bench/cells/<cell>.json``       the limits of the cell's correctness check;
- ``bench/metrics/<metric>.py``     the per-layer metric's reader, with
  ``read(run) -> float | None``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import List, Optional

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES_E2E = {"host_clock", "device_trace"}
SOURCES = SOURCES_E2E | {"program_span", "program_counter"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end",
            "per_layer"}


def load(path: Optional[str] = None) -> dict:
    with open(path or os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s, what, errors):
    if not isinstance(s, str) or not 1 <= len(s) <= 200 or "\n" in s or "\t" in s:
        errors.append(f"{what}: 1 to 200 characters on one line, no tab")


def _keys(entry, allowed, required, what, errors):
    extra = set(entry) - allowed
    missing = required - set(entry)
    if extra or missing:
        errors.append(f"{what}: extra keys {sorted(extra)}, missing {sorted(missing)}")


def validate(m: dict) -> List[str]:
    """The manifest's breaches of the benchmark contract (empty if none)."""
    errors: List[str] = []
    _keys(m, TOP_KEYS, TOP_KEYS, "BENCHMARK.json", errors)
    if errors:
        return errors
    rs = m["run_seconds"]
    if not isinstance(rs, int) or isinstance(rs, bool) or not 1 <= rs <= 51:
        errors.append("run_seconds: a whole number from 1 to 51")
    if not 1 <= len(m["paths"]) <= 16:
        errors.append("paths: 1 to 16 directories")
    for p in m["paths"]:
        if (not re.fullmatch(r"[A-Za-z0-9_.\-/]{1,200}", p) or p.startswith("/")
                or ".." in p.split("/")):
            errors.append(f"paths: bad path {p!r}")
    if not 1 <= len(m["command"]) <= 32:
        errors.append("command: 1 to 32 strings")
    for w in m["command"]:
        _line(w, "command word", errors)
    names = set()

    def name(n, what):
        if not isinstance(n, str) or not NAME.match(n):
            errors.append(f"{what}: bad name {n!r}")
        if n in names:
            errors.append(f"{what}: name {n!r} used twice")
        names.add(n)

    configs = {}
    if not 1 <= len(m["configs"]) <= 24:
        errors.append("configs: 1 to 24 entries")
    for c in m["configs"]:
        _keys(c, {"name", "source", "file", "reduced", "why"},
              {"name", "source", "file", "reduced", "why"}, f"config {c.get('name')}", errors)
        name(c.get("name"), "config")
        _line(c.get("source"), f"config {c.get('name')} source", errors)
        _line(c.get("why"), f"config {c.get('name')} why", errors)
        if len(c.get("reduced", [])) > 16:
            errors.append(f"config {c.get('name')}: at most 16 reduced keys")
        for k in c.get("reduced", []):
            if not NAME.match(k):
                errors.append(f"config {c.get('name')}: bad reduced key {k!r}")
        if not any(c.get("file", "").startswith(p.rstrip("/") + "/") for p in m["paths"]):
            errors.append(f"config {c.get('name')}: file outside paths")
        configs[c.get("name")] = c
    files = [c.get("file") for c in m["configs"]]
    if len(set(files)) != len(files):
        errors.append("configs: two configurations share a file")
    if not 1 <= len(m["workloads"]) <= 24:
        errors.append("workloads: 1 to 24 cells")
    pairs, cells, four = set(), set(), 0
    for w in m["workloads"]:
        what = f"workload {w.get('name')}"
        _keys(w, {"name", "config", "traffic", "chips", "why"},
              {"name", "config", "traffic", "chips", "why"}, what, errors)
        name(w.get("name"), "workload")
        if w.get("config") not in configs:
            errors.append(f"{what}: unknown config {w.get('config')!r}")
        if not isinstance(w.get("traffic"), str) or not NAME.match(w.get("traffic", "")):
            errors.append(f"{what}: bad traffic name")
        if w.get("chips") not in (1, 4):
            errors.append(f"{what}: chips is 1 or 4")
        four += w.get("chips") == 4
        _line(w.get("why"), f"{what} why", errors)
        pair = (w.get("config"), w.get("traffic"))
        if pair in pairs:
            errors.append(f"{what}: config and traffic pair used twice")
        pairs.add(pair)
        cells.add(w.get("name"))
    if four > max(1, len(m["workloads"]) // 2):
        errors.append("workloads: too many four-chip cells")
    used = {w.get("config") for w in m["workloads"]}
    for c in configs:
        if c not in used:
            errors.append(f"config {c}: used by no cell")
    e2e = {}
    if not 1 <= len(m["end_to_end"]) <= 16:
        errors.append("end_to_end: 1 to 16 metrics")
    for e in m["end_to_end"]:
        what = f"metric {e.get('name')}"
        _keys(e, {"name", "unit", "better", "bound", "source", "workloads"},
              {"name", "unit", "better", "bound", "source"}, what, errors)
        name(e.get("name"), "metric")
        _common(e, what, errors, cells)
        if e.get("source") not in SOURCES_E2E:
            errors.append(f"{what}: an end-to-end metric comes from host_clock or device_trace")
        b = e.get("bound")
        if not isinstance(b, (int, float)) or not 0.01 <= b <= 0.25:
            errors.append(f"{what}: bound between 0.01 and 0.25")
        e2e[e.get("name")] = e
    if "setup_s" not in e2e:
        errors.append("end_to_end: setup_s is missing")
    if not 1 <= len(m["per_layer"]) <= 128:
        errors.append("per_layer: 1 to 128 metrics")
    for p in m["per_layer"]:
        what = f"metric {p.get('name')}"
        _keys(p, {"name", "unit", "better", "source", "layer", "moves", "workloads"},
              {"name", "unit", "better", "source", "layer", "moves"}, what, errors)
        name(p.get("name"), "metric")
        _common(p, what, errors, cells)
        if p.get("source") not in SOURCES:
            errors.append(f"{what}: unknown source")
        _line(p.get("layer"), f"{what} layer", errors)
        moved = e2e.get(p.get("moves"))
        if moved is None:
            errors.append(f"{what}: moves no end-to-end metric")
            continue
        for cell in p.get("workloads", sorted(cells)):
            if cell not in reports(moved, cells):
                errors.append(f"{what}: cell {cell} does not report {p['moves']}")
    for cell in cells:
        mine = [e for e in m["end_to_end"] if cell in reports(e, cells)]
        if not any(e["name"] != "setup_s" for e in mine):
            errors.append(f"cell {cell}: reports no end-to-end metric besides setup_s")
        if not any(cell in reports(p, cells) for p in m["per_layer"]):
            errors.append(f"cell {cell}: reports no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        errors.append("BENCHMARK.json is over 64 KiB")
    return errors


def _common(e, what, errors, cells):
    if not isinstance(e.get("unit"), str) or not UNIT.match(e.get("unit", "")):
        errors.append(f"{what}: bad unit")
    if e.get("better") not in ("lower", "higher"):
        errors.append(f"{what}: better is lower or higher")
    for c in e.get("workloads", []):
        if c not in cells:
            errors.append(f"{what}: unknown cell {c!r}")


def reports(metric: dict, cells) -> set:
    """The cells that report this metric."""
    return set(metric.get("workloads", cells))


def workload(m: dict, name: str) -> dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload named {name!r} in BENCHMARK.json")


def metrics_for(m: dict, cell: str, kind: str) -> List[dict]:
    """The ``end_to_end`` or ``per_layer`` metrics this cell reports."""
    cells = [w["name"] for w in m["workloads"]]
    return [e for e in m[kind] if cell in reports(e, cells)]


def config_file(m: dict, config: str) -> dict:
    for c in m["configs"]:
        if c["name"] == config:
            with open(os.path.join(ROOT, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no configuration named {config!r}")


def traffic_file(traffic: str) -> dict:
    with open(os.path.join(BENCH_DIR, "traffic", f"{traffic}.json")) as f:
        return json.load(f)


def cell_file(cell: str) -> dict:
    with open(os.path.join(BENCH_DIR, "cells", f"{cell}.json")) as f:
        return json.load(f)


def metric_reader(metric: str):
    """The ``read`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(BENCH_DIR, "metrics", f"{metric}.py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{metric}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
