"""Scheduler: 95th percentile of TTFT's second term, the grant of a slot to
the dispatch of the request's own first prompt chunk (behind other requests'
chunks), over the requests that reached their first token in the window
(``stats()["ttft_terms"]``)."""


def read(ctx):
    t = ctx.result["counters"].get("ttft_terms")
    if not t or not t["n"]:
        return None
    return 1e3 * t["prefill_blocked_p95_s"]
