"""Scheduler: live slots per decode step over the slots there are, from the
engine's own counters over the window."""


def read(ctx):
    c = ctx.result["counters"]
    if not c.get("decode_steps") or c.get("mean_occupancy") is None:
        return None
    return 100.0 * c["mean_occupancy"] / c["n_slots"]
