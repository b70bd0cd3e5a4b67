"""The pieces of the comparison that decides ``correct``: which requests are
sampled, which rows are malformed, which positions the routing margin leaves
out, and how each number is held to its limit."""

import numpy as np
import pytest

from chipbench import check
from chipbench.stats import RequestRecord


def _rec(i, prompt, new, status="ok", budget=None, tokens=None):
    budget = new if budget is None else budget
    row = np.arange(prompt + budget, dtype=np.int32) % 50 if tokens is None else tokens
    return RequestRecord(index=i, phase="window", prompt_len=prompt, budget=budget, due_s=0.0,
                         submit_s=0.0, status=status, new_tokens=new, tokens=row,
                         first_token_s=1.0, done_s=2.0)


def test_the_sample_holds_the_longest_and_is_drawn_from_the_seed():
    recs = [_rec(i, 10 + i, 5) for i in range(20)] + [_rec(20, 5, 5, status="failed")]
    a = check.pick_sample(recs, 7, 4)
    assert len(a) == 4 and a[0].index == 19          # the longest finished request, first
    assert all(r.status == "ok" for r in a)
    assert [r.index for r in a] == [r.index for r in check.pick_sample(recs, 7, 4)]
    assert [r.index for r in a] != [r.index for r in check.pick_sample(recs, 2**31 + 9, 4)]
    assert check.pick_sample([_rec(0, 5, 5, status=None)], 7, 4) == []
    assert len(check.pick_sample(recs[:2], 7, 4)) == 2   # fewer finished than asked for


@pytest.mark.parametrize("rec,bad", [
    (_rec(0, 8, 4), 0),
    (_rec(0, 8, 3, budget=4), 1),                                  # stopped short of its budget
    (_rec(0, 8, 4, tokens=np.zeros((11,), np.int32)), 1),          # a row of the wrong length
    (_rec(0, 8, 4, tokens=np.full((12,), 50, np.int32)), 1),       # an id outside the vocabulary
    (_rec(0, 8, 4, tokens=np.full((12,), -1, np.int32)), 1),
    (_rec(0, 8, 3, budget=4, status="failed"), 0),                 # counted under `failed`
])
def test_a_finished_row_is_prompt_plus_budget_tokens_of_the_vocabulary(rec, bad):
    assert check.row_faults([rec], vocab_size=50) == bad


def test_positions_under_the_routing_margin_are_left_out_by_the_reference_s_rule():
    gaps = np.array([0.1, 5.0, 0.3, 0.0])
    margins = np.array([0.5, 0.001, 0.02, np.inf])
    assert check.gap_numbers(gaps, margins, 0.0) == {
        "gap_max": 5.0, "gap_mean": pytest.approx(1.35), "gap_p95": pytest.approx(4.295)}
    assert check.gap_numbers(gaps, margins, 0.01) == {
        "gap_max": pytest.approx(0.3), "gap_mean": pytest.approx(0.4 / 3),
        "gap_p95": pytest.approx(0.28)}
    assert check.gap_numbers(gaps, margins, 0.01, "control_").keys() == {
        "control_gap_max", "control_gap_mean", "control_gap_p95"}
    assert check.gap_numbers(gaps, np.zeros(4), 0.01) == {}     # nothing left to compare


@pytest.mark.parametrize("numbers,correct", [
    ({"gap_max": 0.2, "failed": 0}, True),
    ({"gap_max": 0.5, "failed": 0}, True),          # at the limit is inside it
    ({"gap_max": 0.51, "failed": 0}, False),
    ({"gap_max": 0.2, "failed": 1}, False),
    ({"failed": 0}, False),                          # a number the run could not produce
    ({"gap_max": float("nan"), "failed": 0}, False),
    ({"gap_max": None, "failed": 0}, False),
])
def test_each_number_is_held_to_its_own_limit(numbers, correct):
    ok, compared = check.judge(numbers, {"gap_max": 0.5, "failed": 0})
    assert ok is correct
    assert [c["name"] for c in compared] == ["gap_max", "failed"]
    assert all(c["limit"] == {"gap_max": 0.5, "failed": 0}[c["name"]] for c in compared)
