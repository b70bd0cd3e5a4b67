"""Kernels, serving: the least time the chip could take over one decode step's
bytes (every matmul weight the step uses, once, and the cache rows that are
live; the family's arithmetic) at the peak bandwidth, over the step's measured
device time. Memory-bound: at 32 rows the step's FLOPs need far less time.

Live rows per step are read from the requests that finished: their mean rows
while decoding (prompt + half the output), weighted by how long each decoded,
times the mean number of live slots."""


def live_rows(ctx) -> float | None:
    c = ctx.result["counters"]
    done = [r for r in ctx.result["records"]
            if r.status == "ok" and r.tpot_s is not None]
    if not done or not c.get("mean_occupancy"):
        return None
    weight = [r.done_s - r.first_token_s for r in done]
    rows = [r.prompt_len + r.new_tokens / 2 for r in done]
    return c["mean_occupancy"] * sum(w * x for w, x in zip(weight, rows)) / sum(weight)


def read(ctx):
    p = ctx.program("jit_decode")
    rows = live_rows(ctx)
    if p is None or rows is None or not ctx.peaks:
        return None
    needed_s = ctx.cell.family.decode_step_bytes(ctx.cell.config, rows) / ctx.peaks[
        "hbm_bytes_per_s"]
    return 100.0 * needed_s / (p["total_s"] / p["count"])
