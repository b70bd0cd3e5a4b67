"""Model step, serving: mean device duration of the ``jit_prefill`` programs
(one per ladder rung) in the traced slice, over all chunks."""


def read(ctx):
    p = ctx.program("jit_prefill")
    return None if p is None else 1e3 * p["total_s"] / p["count"]
