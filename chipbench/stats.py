"""From request and step records to the end-to-end numbers. No JAX here.

A rate is over all the work and all the seconds of the window; a tail is the
tail of all requests due in it. Nothing here takes a median of chunks or
gaps: a stall inside the window moves every number this file returns.
"""

from __future__ import annotations

import dataclasses
import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0-100) by linear interpolation between order
    statistics; of nothing, an error."""
    xs = sorted(float(v) for v in values)
    if not xs:
        raise ValueError("percentile of no values")
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


@dataclasses.dataclass
class RequestRecord:
    """One request as the load generator saw it. Times are seconds on the
    harness's clock, relative to the window's opening (negative: the ramp)."""

    index: int
    phase: str                    # "ramp" or "window"
    prompt_len: int
    budget: int
    due_s: float                  # when it should have been sent
    submit_s: float               # when it was
    status: str | None = None     # None: still in flight when the run ended
    new_tokens: int = 0
    first_token_s: float | None = None
    done_s: float | None = None
    tokens: object = None         # the served row (prompt + output), for `correct`

    def shift(self, dt: float) -> None:
        """Moves every time of this record by ``dt`` seconds."""
        for name in ("due_s", "submit_s", "first_token_s", "done_s"):
            if getattr(self, name) is not None:
                setattr(self, name, getattr(self, name) + dt)

    @property
    def ttft_s(self) -> float | None:
        return None if self.first_token_s is None else self.first_token_s - self.due_s

    @property
    def tpot_s(self) -> float | None:
        if self.first_token_s is None or self.done_s is None or self.new_tokens < 2:
            return None
        return (self.done_s - self.first_token_s) / (self.new_tokens - 1)


def tokens_emitted(r: RequestRecord, lo: float, hi: float) -> float:
    """Output tokens of a finished request that were produced in [lo, hi]. The
    engine hands a row over only when it is finished, so the times of its
    tokens are read off the line from its first token to its last: token k at
    ``first_token_s + k * tpot``."""
    if r.status != "ok" or r.first_token_s is None or r.done_s is None or r.new_tokens < 1:
        return 0.0
    if r.new_tokens == 1 or r.done_s <= r.first_token_s:
        return float(r.new_tokens) if lo <= r.first_token_s <= hi else 0.0
    step = (r.done_s - r.first_token_s) / (r.new_tokens - 1)
    first = max(0, math.ceil((lo - r.first_token_s) / step - 1e-9))
    last = min(r.new_tokens - 1, math.floor((hi - r.first_token_s) / step + 1e-9))
    return float(max(0, last - first + 1))


def serve_summary(records: list[RequestRecord], window_s: float, end_s: float) -> dict:
    """The serving numbers of one run. ``end_s`` is when the run stopped
    waiting (the close of the window, or the end of the drain): a request that
    never produced a first token counts as having waited until then.

    - ``serve_tok_s``: output tokens that requests which finished ``ok``
      produced inside the window (``tokens_emitted``), over the window's
      seconds: the ramp's requests count for what they produced after the
      opening, the window's for what they produced before the close.
    - ``ttft_p95_ms``: over all requests due in the window; one that is not
      ``ok`` counts as the worst.
    - ``tpot_p95_ms``: over all finished requests due in the window or
      finished in it.
    """
    in_window = [r for r in records if r.phase == "window"]
    done_inside = [r for r in records
                   if r.status == "ok" and r.done_s is not None and 0.0 <= r.done_s <= window_s]
    waited = [r.ttft_s if r.status == "ok" else None for r in in_window]
    worst = max([t for t in waited if t is not None]
                + [end_s - r.due_s for r, t in zip(in_window, waited) if t is None] + [0.0])
    ttfts = [worst if t is None else t for t in waited]
    tpot_of = {id(r): r.tpot_s for r in in_window + done_inside}
    tpots = [t for t in tpot_of.values() if t is not None]
    out = {
        "attempted": len(in_window),
        "failed": sum(1 for r in in_window if r.status not in (None, "ok")),
        "unfinished": sum(1 for r in in_window if r.status is None),
        "finished_in_window": len(done_inside),
        "tokens_out_in_window": sum(r.new_tokens for r in done_inside),
        "serve_tok_s": sum(tokens_emitted(r, 0.0, window_s) for r in records) / window_s,
        "n_ttft": len(ttfts),
        "n_tpot": len(tpots),
    }
    if ttfts:
        out["ttft_p50_ms"] = 1e3 * percentile(ttfts, 50)
        out["ttft_p95_ms"] = 1e3 * percentile(ttfts, 95)
    if tpots:
        out["tpot_p50_ms"] = 1e3 * percentile(tpots, 50)
        out["tpot_p95_ms"] = 1e3 * percentile(tpots, 95)
    late = [r.submit_s - r.due_s for r in in_window]
    if late:
        out["gen_late_p95_ms"] = 1e3 * percentile(late, 95)
    return out
