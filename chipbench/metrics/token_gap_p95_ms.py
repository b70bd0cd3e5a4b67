"""Scheduler: 95th percentile, over the tokens fetched in the window, of the
wait since the same request's previous token (``stats()["token_gap"]``): the
per-token tail that ``tpot_p95_ms``, a mean per request, averages away."""


def read(ctx):
    g = ctx.result["counters"].get("token_gap")
    if not g or not g["n"]:
        return None
    return 1e3 * g["p95_s"]
