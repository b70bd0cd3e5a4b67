"""Scheduler: host milliseconds of a tick, the tick's wall time less the time
it blocked in the two fetches, from the engine's ``serving.*`` phase spans
(``stats()["tick_phases"]``) over the window."""


def read(ctx):
    p = ctx.result["counters"].get("tick_phases")
    if not p or not p["ticks"]:
        return None
    return 1e3 * p["host_s"] / p["ticks"]
