"""BENCHMARK.json against the contract, and every name finding its files."""
import copy
import os

import pytest

from bench import manifest, peaks

M = manifest.load()


def test_the_manifest_meets_the_contract():
    assert manifest.validate(M) == []


def test_every_entry_finds_its_files():
    for w in M["workloads"]:
        cfg = manifest.config_file(M, w["config"])
        assert cfg["name"] == w["config"]
        traffic = manifest.traffic_file(w["traffic"])
        assert traffic["generator"] == "open_loop"
        limits = manifest.cell_file(w["name"])["limits"]
        assert limits and set(limits) <= {"max_gap", "mean_gap", "wrong_length"}
        assert limits.get("wrong_length") == 0
    for c in M["configs"]:
        cfg = manifest.config_file(M, c["name"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert cfg["source"] == c["source"]
    for p in M["per_layer"]:
        assert callable(manifest.metric_reader(p["name"]))


def test_the_command_names_only_files_under_paths():
    words = [w for w in M["command"] if "/" in w]
    assert words and all(w.split("/")[0] in M["paths"] for w in words)
    assert all(os.path.exists(os.path.join(manifest.ROOT, w)) for w in words)


def broken(edit):
    m = copy.deepcopy(M)
    edit(m)
    return manifest.validate(m)


@pytest.mark.parametrize("edit", [
    lambda m: m.update(run_seconds=52),
    lambda m: m.update(extra=1),
    lambda m: m["workloads"][0].update(name="has space"),
    lambda m: m["workloads"][0].update(name="a/b"),
    lambda m: m["workloads"][1].update(name=m["workloads"][0]["name"]),
    lambda m: m["workloads"][0].update(chips=2),
    lambda m: m["workloads"][0].update(config="nope"),
    lambda m: m["workloads"].append(dict(m["workloads"][0], name="dup.pair")),
    lambda m: m["end_to_end"][1].update(bound=0.3),
    lambda m: m["end_to_end"][1].update(source="program_counter"),
    lambda m: m["end_to_end"].pop(0),
    lambda m: m["end_to_end"][1].update(unit="tokens per second"),
    lambda m: m["per_layer"][0].update(moves="nope"),
    lambda m: m["per_layer"][0].update(better="up"),
    lambda m: m["per_layer"][0].update(why="x"),
    lambda m: m["end_to_end"][2].update(workloads=["smollm-360m-bika.chat"]),
    lambda m: m["configs"][0].update(why="two\nlines"),
    lambda m: m["configs"][0].update(file="elsewhere/x.json"),
    lambda m: m["paths"].append("../out"),
], ids=["run_seconds", "top_key", "space", "slash", "dup_name", "chips", "config",
        "dup_pair", "bound", "e2e_source", "no_setup", "unit", "moves", "better",
        "extra_key", "moves_unreported", "why_lines", "file_outside", "path_escapes"])
def test_breaches_are_refused(edit):
    assert broken(edit)


def test_an_unknown_device_has_no_peaks():
    assert peaks.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        peaks.peaks("cpu")
