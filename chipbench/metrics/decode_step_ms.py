"""Model step, serving: mean device duration of the ``jit_decode`` program in
the traced slice."""


def read(ctx):
    p = ctx.program("jit_decode")
    return None if p is None else 1e3 * p["total_s"] / p["count"]
