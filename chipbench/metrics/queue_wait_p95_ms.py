"""Scheduler: 95th percentile of TTFT's first term, submit to the grant of a
slot, over the requests that reached their first token in the window
(``stats()["ttft_terms"]``)."""


def read(ctx):
    t = ctx.result["counters"].get("ttft_terms")
    if not t or not t["n"]:
        return None
    return 1e3 * t["queue_wait_p95_s"]
