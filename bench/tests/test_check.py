"""The correctness check's pieces: packing a sample into reference rows,
and the gap of each served token below the reference's best."""
import numpy as np
import pytest

from bench import check


def test_rows_hold_the_longest_and_the_required_first():
    lengths = {1: 50, 2: 200, 3: 120, 4: 30, 5: 90}
    rows = check.pick_rows(lengths, 256, 2, np.random.default_rng(0), must=[4])
    flat = [r for row in rows for r in row]
    assert flat[0] == 2 and 4 in flat
    assert len(rows) <= 2
    assert all(sum(lengths[r] for r in row) <= 256 for row in rows)
    assert len(flat) == len(set(flat))


def test_rows_skip_what_cannot_fit():
    rows = check.pick_rows({1: 300, 2: 100}, 256, 1, np.random.default_rng(0))
    assert rows == [[2]]
    assert check.pick_rows({1: 300}, 256, 1, np.random.default_rng(0)) == []


def test_pack_row_puts_each_served_token_after_its_context():
    prompts = {7: np.array([5, 6, 7]), 8: np.array([9])}
    outputs = {7: np.array([1, 2]), 8: np.array([3, 4, 5])}
    tokens, pos, seg, spans = check.pack_row([7, 8], prompts, outputs, 16)
    assert tokens[:4].tolist() == [5, 6, 7, 1] and tokens[4:7].tolist() == [9, 3, 4]
    assert pos[:7].tolist() == [0, 1, 2, 3, 0, 1, 2]
    assert seg[:7].tolist() == [0, 0, 0, 0, 1, 1, 1] and (seg[7:] == -1).all()
    # the logits at the prompt's last token predict the first served token
    assert spans[7].tolist() == [2, 3] and spans[8].tolist() == [4, 5, 6]


def test_gaps_are_zero_for_the_best_token_only():
    logits = np.array([[0.0, 2.0, 1.0], [3.0, 0.5, 0.0]])
    gaps = check.served_gaps(logits, {1: np.array([0, 1])}, {1: np.array([1, 1])})
    assert gaps[1].tolist() == [0.0, 2.5]


def test_expected_length_is_capped_at_the_cache_end():
    assert check.expected_len(10, 5, 256) == 5
    assert check.expected_len(250, 20, 256) == 7


CFG = {"serve": {"max_len": 64}, "check": {"reference_row_tokens": 32, "reference_rows": 1}}
LIMITS = {"max_gap": 0.5, "wrong_length": 0}


def prefers_2(cfg, seed, tokens, positions, segments):
    lg = np.zeros((len(tokens), 4))
    lg[:, 2] = 1.0  # the reference always prefers token 2
    return lg


def test_compare_fails_a_wrong_token_and_a_short_output():
    cfg, ref = CFG, prefers_2
    prompts = {0: np.array([1, 2, 3])}
    good = check.compare(cfg, 0, prompts, {0: np.array([2, 2])}, {0: 2}, LIMITS, ref_logits=ref)
    assert good["correct"] and good["numbers"]["max_gap"]["value"] == 0.0
    wrong = check.compare(cfg, 0, prompts, {0: np.array([2, 3])}, {0: 2}, LIMITS, ref_logits=ref)
    assert not wrong["correct"] and wrong["numbers"]["max_gap"]["value"] == 1.0
    short = check.compare(cfg, 0, prompts, {0: np.array([2])}, {0: 2}, LIMITS, ref_logits=ref)
    assert not short["correct"] and short["numbers"]["wrong_length"]["value"] == 1


def test_a_cell_compares_only_its_own_numbers():
    prompts = {0: np.array([1, 2, 3])}
    outputs = {0: np.array([2, 3, 2, 2])}  # one of four served tokens 1 below the best
    res = check.compare(CFG, 0, prompts, outputs, {0: 4}, {"mean_gap": 0.3, "wrong_length": 0},
                        ref_logits=prefers_2)
    assert sorted(res["numbers"]) == ["mean_gap", "wrong_length"]
    assert res["numbers"]["mean_gap"]["value"] == 0.25 and res["correct"]
    assert res["printed"] == {"max_gap": 1.0}
    res = check.compare(CFG, 0, prompts, outputs, {0: 4}, {"mean_gap": 0.2},
                        ref_logits=prefers_2)
    assert not res["correct"]


def test_a_limit_on_an_unknown_number_is_an_error():
    with pytest.raises(KeyError):
        check.compare(CFG, 0, {0: np.array([1])}, {0: np.array([2])}, {0: 1},
                      {"recall": 0.9}, ref_logits=prefers_2)
