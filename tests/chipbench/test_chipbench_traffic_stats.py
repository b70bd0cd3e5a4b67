"""Seeded traffic and the arithmetic from records to end-to-end numbers."""

import numpy as np
import pytest

from chipbench import stats, traffic
from chipbench.stats import RequestRecord

OPEN = {
    "arrivals": {"kind": "open", "rate_per_s": 5.0, "cv": 1.0},
    "prompt_len": {"dist": "lognormal", "median": 384, "sigma": 0.8, "min": 32, "max": 1536},
    "output_len": {"dist": "lognormal", "median": 96, "sigma": 0.7, "min": 16, "max": 384},
}
CLOSED = {
    "arrivals": {"kind": "closed", "clients": 64, "pool": 128},
    "prompt_len": {"dist": "loguniform", "min": 64, "max": 512},
    "output_len": {"dist": "loguniform", "min": 128, "max": 512},
}
BIG_SEED = 2**31 + 12345  # the driver's seeds pass 32 signed bits


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_requests(mix):
    a = traffic.requests_for_phase(mix, 32768, BIG_SEED, 40)
    b = traffic.requests_for_phase(mix, 32768, BIG_SEED, 40)
    assert len(a) == len(b) >= 100
    for x, y in zip(a, b):
        assert x.budget == y.budget and x.due_s == y.due_s
        np.testing.assert_array_equal(x.prompt, y.prompt)


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_lengths_stay_in_their_clips_and_ids_in_the_vocabulary(mix):
    reqs = traffic.requests_for_phase(mix, 1000, 7, 40)
    p, o = mix["prompt_len"], mix["output_len"]
    assert all(p["min"] <= r.prompt.size <= p["max"] for r in reqs)
    assert all(o["min"] <= r.budget <= o["max"] for r in reqs)
    assert all(0 <= r.prompt.min() and r.prompt.max() < 1000 for r in reqs)
    assert len({r.prompt.size for r in reqs}) > 20  # a distribution, not one length


def test_every_seed_offers_the_same_work_in_another_order():
    a = traffic.requests_for_phase(OPEN, 32768, 1, 40)
    b = traffic.requests_for_phase(OPEN, 32768, BIG_SEED, 40)
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in b)
    assert sorted(r.budget for r in a) == sorted(r.budget for r in b)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in b]

    def gaps(rs):   # the first arrival comes half its gap in
        due = [r.due_s for r in rs]
        return np.sort(np.concatenate([[2 * due[0]], np.diff(due)]))

    np.testing.assert_allclose(gaps(a), gaps(b), rtol=1e-9)


def test_open_loop_arrivals_fill_the_window_at_the_stated_rate():
    reqs = traffic.requests_for_phase(OPEN, 32768, 3, 40)
    due = [r.due_s for r in reqs]
    assert len(reqs) == 200 and due == sorted(due)
    assert 0.0 < due[0] and 39.0 < due[-1] < 40.0
    burst = dict(OPEN, arrivals={"kind": "open", "rate_per_s": 5.0, "cv": 3.0})
    g = np.diff([r.due_s for r in traffic.requests_for_phase(burst, 32768, 3, 40)])
    assert np.std(g) / np.mean(g) > 1.5  # burstier than Poisson at the same mean rate


def test_ramp_and_window_draw_different_requests():
    a = traffic.requests_for_phase(OPEN, 32768, 5, 10)
    b = traffic.requests_for_phase(OPEN, 32768, 5, 10, salt=1)
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))


def test_percentile_interpolates():
    assert stats.percentile([1, 2, 3, 4, 5], 50) == 3
    assert stats.percentile(range(101), 95) == 95
    assert stats.percentile([10.0], 95) == 10.0
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def _records(stall_at=None, stall_s=0.0, n=200, rate=5.0):
    """A synthetic run: a request every 1/rate s, 0.2 s to its first token,
    150 tokens at 30 ms each. A stall freezes every request alive at
    ``stall_at`` for ``stall_s``."""
    out = []
    for i in range(n):
        due = i / rate
        first, done = due + 0.2, due + 0.2 + 149 * 0.03
        if stall_at is not None:
            if due <= stall_at < first:
                first += stall_s
                done += stall_s
            elif first <= stall_at < done:
                done += stall_s
            elif stall_at <= due < stall_at + stall_s:   # queued behind the stall
                first += stall_at + stall_s - due
                done += stall_at + stall_s - due
        out.append(RequestRecord(index=i, phase="window", prompt_len=100, budget=150,
                                 due_s=due, submit_s=due + 0.001, status="ok",
                                 new_tokens=150, first_token_s=first, done_s=done))
    return out


def test_summary_of_a_steady_run():
    s = stats.serve_summary(_records(), 40.0, 42.0)
    assert s["attempted"] == 200 and s["failed"] == 0
    assert s["ttft_p95_ms"] == pytest.approx(200.0)
    assert s["tpot_p95_ms"] == pytest.approx(30.0)
    assert s["gen_late_p95_ms"] == pytest.approx(1.0)
    # what is produced after the close counts toward no rate
    inside = sum(1 for r in _records() for k in range(150)
                 if r.first_token_s + k * 0.03 <= 40.0)
    assert s["serve_tok_s"] == pytest.approx(inside / 40.0, rel=1e-3)
    assert inside < 200 * 150


@pytest.mark.parametrize("lo,hi,want", [(0.0, 100.0, 11), (1.0, 1.35, 4), (1.95, 2.0, 1),
                                        (-5.0, 0.99, 0), (2.01, 9.0, 0), (1.0, 1.0, 1)])
def test_tokens_emitted_lie_on_the_line_from_the_first_to_the_last(lo, hi, want):
    r = RequestRecord(index=0, phase="ramp", prompt_len=8, budget=11, due_s=0.5, submit_s=0.5,
                      status="ok", new_tokens=11, first_token_s=1.0, done_s=2.0)
    assert stats.tokens_emitted(r, lo, hi) == want


def test_tokens_of_a_request_that_is_not_ok_count_for_nothing():
    r = RequestRecord(index=0, phase="window", prompt_len=8, budget=11, due_s=0.5,
                      submit_s=0.5, status="failed", new_tokens=5, first_token_s=1.0,
                      done_s=2.0)
    assert stats.tokens_emitted(r, 0.0, 10.0) == 0.0


def test_a_fixed_schedule_is_the_same_under_every_seed_but_for_the_token_ids():
    mix = dict(OPEN, schedule_seed=7)
    a = traffic.requests_for_phase(mix, 32768, 1, 40)
    b = traffic.requests_for_phase(mix, 32768, BIG_SEED, 40)
    assert [r.prompt.size for r in a] == [r.prompt.size for r in b]
    assert [r.budget for r in a] == [r.budget for r in b]
    assert [r.due_s for r in a] == [r.due_s for r in b]
    assert any(not np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    other = traffic.requests_for_phase(dict(OPEN, schedule_seed=8), 32768, 1, 40)
    assert [r.prompt.size for r in a] != [r.prompt.size for r in other]
    assert sorted(r.prompt.size for r in a) == sorted(r.prompt.size for r in other)


def test_a_record_moves_whole_when_the_window_opens_after_a_counted_ramp():
    r = RequestRecord(index=0, phase="ramp", prompt_len=8, budget=4, due_s=2.0, submit_s=2.5,
                      first_token_s=3.0)
    r.shift(-10.0)
    assert (r.due_s, r.submit_s, r.first_token_s, r.done_s) == (-8.0, -7.5, -7.0, None)
    assert r.ttft_s == pytest.approx(1.0)   # a difference of times does not move
