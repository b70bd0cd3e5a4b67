"""Model step, serving: the share of the KV buffer a decode step had to read.
The engine counts the rows its decoding slots hold at every step
(``stats()["cache"]["live_rows_mean"]``, summed over the slots); the buffer is
``n_slots x max_len`` rows of every plane, and a step's attention lifts out
and scores all of it whatever is live. An engine that does not count them
(no ``cache`` block in its stats) gives nothing to read."""


def read(ctx):
    cache = ctx.result["counters"].get("cache")
    if not cache or cache.get("live_rows_mean") is None:
        return None
    eng = ctx.cell.workload["engine"]
    return 100.0 * cache["live_rows_mean"] / (eng["n_slots"] * eng["max_len"])
