"""Whole step, serving: model FLOPs of the work done in the window (active
parameters only, attention over live rows; the family's arithmetic) over
window seconds times the peak. A request's prompt counts where its first token
falls in the window; its decoding counts by the share of its output tokens
produced in the window, the same tokens as ``serve_tok_s`` counts, so the two
move together."""

from chipbench import stats


def read(ctx):
    r = ctx.result
    flops_of = lambda x, n: ctx.cell.family.request_flops(ctx.cell.config, x.prompt_len, n)
    flops = 0.0
    for x in r["records"]:
        if x.status != "ok" or x.first_token_s is None:
            continue
        first_inside = 0.0 <= x.first_token_s <= r["window_s"]
        if first_inside:
            flops += flops_of(x, 1)
        if x.new_tokens > 1:
            later = stats.tokens_emitted(x, 0.0, r["window_s"]) - first_inside
            flops += (flops_of(x, x.new_tokens) - flops_of(x, 1)) * later / (x.new_tokens - 1)
    if flops <= 0.0 or not ctx.peaks:   # no peak (a rehearsal's backend): nothing to read
        return None
    return 100.0 * flops / (r["window_s"] * ctx.peaks["bf16_flops"])
