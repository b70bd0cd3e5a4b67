"""Seeded traffic: one general generator, driven by the ``traffic`` block of a
cell's file. A new mix is a new data file; nothing here names a cell.

Every seed gets the same multiset of prompt lengths, output budgets and
inter-arrival gaps (the evenly spaced quantiles of the stated distributions)
in another order, so two seeds differ in who arrives when and never in how
much work there is. Token ids are uniform draws from the seed.

A length is ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``
or ``{"dist": "loguniform", "min": a, "max": b}`` or ``{"dist": "fixed", "value": v}``.
Arrivals are ``{"kind": "open", "rate_per_s": r, "cv": c}`` (gamma gaps with
coefficient of variation c; c = 1 is Poisson) or ``{"kind": "closed",
"clients": n, "pool": p}``.

``"schedule_seed": s`` (optional) fixes the schedule: the order of lengths and
gaps is then drawn from ``s`` and is the same under every ``--seed``, which
still draws the token ids (and the weights). Without the key a seed reorders
the whole phase. PERF.md, section 4, says why a cell would fix it.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np

# Splits one --seed into independent streams; a new purpose takes a new tag.
_STREAMS = {"prompt_order": 1, "output_order": 2, "gap_order": 3, "token_ids": 4,
            "sample": 5}


def rng_for(seed: int, stream: str, salt: int = 0) -> np.random.Generator:
    """A generator for one purpose under one seed. Seeds may exceed 2**31."""
    return np.random.default_rng([int(seed), _STREAMS[stream], int(salt)])


def order_for(traffic: dict, n: int, seed: int, stream: str, salt: int) -> np.ndarray:
    """The order of the n quantiles of one stream: drawn from the mix's
    ``schedule_seed`` where it has one, else from ``seed``."""
    return rng_for(traffic.get("schedule_seed", seed), stream, salt).permutation(n)


def _quantile_points(n: int) -> np.ndarray:
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def _norm_ppf(q: np.ndarray) -> np.ndarray:
    from scipy.special import ndtri

    return ndtri(q)


def length_quantiles(spec: dict, n: int) -> np.ndarray:
    """The n evenly spaced quantiles of a length distribution, as whole
    numbers inside its clips, in rising order."""
    q = _quantile_points(n)
    dist = spec["dist"]
    if dist == "lognormal":
        x = float(spec["median"]) * np.exp(float(spec["sigma"]) * _norm_ppf(q))
    elif dist == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        x = np.exp(lo + (hi - lo) * q)
    elif dist == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    else:
        raise ValueError(f"unknown length distribution {dist!r}")
    return np.clip(np.rint(x), int(spec["min"]), int(spec["max"])).astype(np.int64)


def gap_quantiles(rate_per_s: float, cv: float, n: int) -> np.ndarray:
    """The n evenly spaced quantiles of the gap between arrivals (gamma with
    mean 1/rate and the given coefficient of variation), scaled so that they
    sum to exactly n / rate: every seed then offers the same load."""
    from scipy.special import gammaincinv

    shape = 1.0 / (float(cv) ** 2)
    g = gammaincinv(shape, _quantile_points(n)) / shape  # mean ~1
    return g * (n / g.sum()) / float(rate_per_s)


@dataclasses.dataclass
class Request:
    index: int
    prompt: np.ndarray      # int32 token ids
    budget: int             # output tokens (no EOS: the budget is the length)
    due_s: float | None     # seconds after the phase opens; None in a closed loop


def make_requests(traffic: dict, vocab_size: int, seed: int, n: int, *,
                  salt: int = 0) -> list[Request]:
    """n requests for one phase (``salt`` tells the ramp from the window)."""
    prompts = length_quantiles(traffic["prompt_len"], n)
    budgets = length_quantiles(traffic["output_len"], n)
    prompts = prompts[order_for(traffic, n, seed, "prompt_order", salt)]
    budgets = budgets[order_for(traffic, n, seed, "output_order", salt)]
    arr = traffic["arrivals"]
    if arr["kind"] == "open":
        gaps = gap_quantiles(arr["rate_per_s"], arr.get("cv", 1.0), n)
        gaps = gaps[order_for(traffic, n, seed, "gap_order", salt)]
        # the first arrival comes half a gap in, so the last is inside n / rate
        due = np.cumsum(gaps) - gaps[0] / 2
    elif arr["kind"] == "closed":
        due = [None] * n
    else:
        raise ValueError(f"unknown arrival kind {arr['kind']!r}")
    ids = rng_for(seed, "token_ids", salt)
    return [
        Request(i, ids.integers(0, vocab_size, size=int(prompts[i]), dtype=np.int32),
                int(budgets[i]), None if due[i] is None else float(due[i]))
        for i in range(n)
    ]


def requests_for_phase(traffic: dict, vocab_size: int, seed: int, seconds: float, *,
                       salt: int = 0) -> list[Request]:
    """The requests of one phase of ``seconds``: rate x seconds of them in an
    open loop, the cell's whole pool in a closed one."""
    arr = traffic["arrivals"]
    if arr["kind"] == "open":
        n = max(1, int(round(float(arr["rate_per_s"]) * float(seconds))))
    else:
        n = int(arr["pool"])
    return make_requests(traffic, vocab_size, seed, n, salt=salt)
