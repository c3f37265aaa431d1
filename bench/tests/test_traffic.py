"""The traffic generator: seed determinism, the same work for every seed,
and the length and arrival statistics each traffic file asks for."""
import json
import os
from collections import Counter

import numpy as np
import pytest

from bench import traffic_gen

TRAFFIC_DIR = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                           "traffic")
MIXES = sorted(f[:-5] for f in os.listdir(TRAFFIC_DIR) if f.endswith(".json"))


def load(name):
    with open(os.path.join(TRAFFIC_DIR, f"{name}.json")) as f:
        return json.load(f)


def sizes(items):
    return Counter((len(i.prompt), i.max_new_tokens) for i in items)


@pytest.mark.parametrize("mix", MIXES)
def test_same_seed_same_requests(mix):
    t = load(mix)
    a = traffic_gen.generate(t, 2**31 + 12345, 1000, 80)
    b = traffic_gen.generate(t, 2**31 + 12345, 1000, 80)
    assert [(i.due_s, i.max_new_tokens, i.prompt.tolist()) for i in a] == \
           [(i.due_s, i.max_new_tokens, i.prompt.tolist()) for i in b]


@pytest.mark.parametrize("mix", MIXES)
def test_every_seed_offers_the_same_work_in_another_order(mix):
    t = load(mix)
    n = 2 * t["block"]
    a = traffic_gen.generate(t, 1, 1000, n)
    b = traffic_gen.generate(t, 2, 1000, n)
    assert sizes(a) == sizes(b)
    assert [i.max_new_tokens for i in a] != [i.max_new_tokens for i in b]
    gaps = lambda items: sorted(np.round(np.diff([0.0] + [i.due_s for i in items]), 9))
    assert gaps(a) == gaps(b)
    assert a[-1].due_s == pytest.approx(b[-1].due_s)


@pytest.mark.parametrize("mix", MIXES)
def test_lengths_and_rate_follow_the_file(mix):
    t = load(mix)
    items = traffic_gen.generate(t, 7, 1000, t["block"])
    pre = t["prefix"]["length"] if t["prefix"]["kind"] == "system_prompts" else 0
    plen = np.array([len(i.prompt) - pre for i in items])
    olen = np.array([i.max_new_tokens for i in items])
    for spec, v in ((t["prompt"], plen), (t["output"], olen)):
        assert v.min() >= spec["min"] and v.max() <= spec["max"]
        if spec["dist"] == "lognormal":
            assert np.median(v) == pytest.approx(spec["median"], rel=0.05)
        else:  # log-uniform: the median is the geometric mean of the ends
            assert np.median(v) == pytest.approx((spec["min"] * spec["max"]) ** 0.5, rel=0.05)
    # stratified exponential gaps: the block's mean gap is 1/rate within
    # what the (i + 0.5)/n quantiles leave out of the tail
    assert items[-1].due_s / len(items) == pytest.approx(1 / t["rate_per_s"], rel=0.1)
    assert all(0 <= tok < 1000 for i in items for tok in i.prompt)


def test_lognormal_quantiles_are_heavy_tailed():
    v = traffic_gen.quantile_lengths({"dist": "lognormal", "median": 256, "sigma": 1.0,
                                      "min": 1, "max": 10**6}, 1000)
    assert np.median(v) == pytest.approx(256, rel=0.01)
    assert np.percentile(v, 95) == pytest.approx(256 * np.exp(1.645), rel=0.02)
    assert v.mean() > 1.5 * np.median(v)


def test_zipf_counts_sum_and_skew():
    c = traffic_gen.zipf_counts(4, 1.1, 64)
    assert c.sum() == 64 and list(c) == sorted(c, reverse=True)
    w = 1 / np.arange(1, 5) ** 1.1
    assert np.allclose(c / 64, w / w.sum(), atol=1 / 64)


def test_system_prompts_are_shared_prefixes():
    t = load("smollm-sysprompt")
    items = traffic_gen.generate(t, 3, 1000, t["block"])
    n = t["prefix"]["length"]
    by_pid = {}
    for i in items:
        by_pid.setdefault(i.prefix_id, set()).add(i.prompt[:n].tobytes())
    assert len(by_pid) == t["prefix"]["count"]
    assert all(len(v) == 1 for v in by_pid.values())
    counts = Counter(i.prefix_id for i in items)
    assert counts[0] > counts[3]


def test_requests_needed_covers_the_window():
    t = load("smollm-chat")
    n = traffic_gen.requests_needed(t, 40)
    items = traffic_gen.generate(t, 5, 1000, n)
    w = t["window"]
    assert items[-1].due_s > w["warm_s"] + 40 + w["drain_limit_s"]
