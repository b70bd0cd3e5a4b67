"""Device: 1 - (union of the intervals in which an operation ran on the device)
/ (length of the traced slice, from the first device event to the last)."""


def read(ctx):
    if not ctx.trace or not ctx.trace["window_s"]:
        return None
    return 100.0 * (1.0 - ctx.trace["busy_s"] / ctx.trace["window_s"])
