"""Load generator: 95th percentile of (actual submit - due time) over the
requests due in the window. A starved generator must not read as a fast server."""


def read(ctx):
    return ctx.result["summary"].get("gen_late_p95_ms")
