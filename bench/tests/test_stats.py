"""Metric arithmetic: pooled inter-token gaps, time to first token from the
due time, and the window's edges."""
import pytest

from bench import stats
from bench.stats import ReqRecord, Window

W = Window(10.0, 20.0)


def rec(rid, due, times, admit=None):
    r = ReqRecord(rid, due, 8, 4, token_times=list(times))
    r.t_admit = admit
    return r


def test_percentile_is_nearest_rank_from_above():
    assert stats.percentile([3, 1, 2], 0.5) == 2
    assert stats.percentile(list(range(1, 21)), 0.95) == 20
    # rank ceil(0.95 * 99) = 95 (zero-based): never under-reports the tail
    assert stats.percentile(list(range(1, 101)), 0.95) == 96
    with pytest.raises(ValueError):
        stats.percentile([], 0.5)


def test_gaps_are_pooled_not_averaged_per_request():
    # one request with one long stall among short gaps, one steady request:
    # a per-request mean would hide the stall
    a = rec(0, 9.0, [11.0, 11.1, 11.2, 13.2, 13.3])
    b = rec(1, 9.0, [11.0, 11.1, 11.2, 11.3, 11.4])
    gaps = stats.itl_gaps([a, b], W)
    assert sorted(round(g, 6) for g in gaps) == [0.1] * 7 + [2.0]
    assert stats.percentile(gaps, 0.95) == pytest.approx(2.0)


def test_gap_belongs_to_the_window_its_later_token_falls_in():
    r = rec(0, 9.0, [9.5, 10.0, 19.9, 20.0])
    gaps = stats.itl_gaps([r], W)
    # 9.5 -> 10.0 ends at the open (inside); 19.9 -> 20.0 ends at the close (outside)
    assert [round(g, 6) for g in gaps] == [0.5, 9.9]


def test_ttft_is_timed_from_the_due_time():
    late = rec(0, 12.0, [15.0])
    late.t_submit = 14.5  # the generator submitted it late; the wait still counts
    assert stats.ttft_values([late], W, 30.0) == [3.0]


def test_ttft_covers_requests_due_in_the_window_only():
    recs = [rec(0, 9.99, [10.5]), rec(1, 10.0, [10.4]), rec(2, 19.99, [21.0]),
            rec(3, 20.0, [20.1])]
    assert [round(v, 6) for v in stats.ttft_values(recs, W, 30.0)] == [0.4, 1.01]


def test_missing_first_token_counts_as_the_whole_wait():
    recs = [rec(i, 11.0, [11.5]) for i in range(19)] + [rec(19, 12.0, [])]
    vals = stats.ttft_values(recs, W, 75.0)
    assert max(vals) == 63.0
    assert stats.percentile(vals, 0.96) == 63.0


def test_tokens_in_window_and_rate():
    recs = [rec(0, 9.0, [9.9, 10.0, 15.0, 20.0]), rec(1, 11.0, [19.999])]
    assert stats.tokens_in_window(recs, W) == 3
    e2e = stats.end_to_end(recs, W, 30.0)
    assert e2e["out_tok_s"] == pytest.approx(0.3)
    assert e2e["ttft_p95_s"] == pytest.approx(8.999)


def test_queue_wait_from_due_to_slot_claimed():
    recs = [rec(0, 11.0, [12.0], admit=11.25), rec(1, 12.0, [], admit=None),
            rec(2, 5.0, [6.0], admit=5.5)]
    assert stats.queue_waits(recs, W) == [0.25]

