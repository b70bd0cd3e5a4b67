"""Model step, serving: the share of the device's busy time in the traced
slice that went to the ``jit_prefill`` programs; the rest is decode. In a cell
judged on throughput it says how much of a tick the prompts of the requests
that replace finished ones take from the decoding ones. A slice in which the
device was busy and no prompt chunk ran reads 0: there the trace was read and
that is its answer."""


def read(ctx):
    if not ctx.trace or not ctx.trace["busy_s"]:
        return None
    p = ctx.program("jit_prefill")
    return 100.0 * (p["total_s"] if p else 0.0) / ctx.trace["busy_s"]
