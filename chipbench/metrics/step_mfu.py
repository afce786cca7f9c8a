"""The whole traced window's share of the chips' bf16 peak: live tokens
times required FLOPs per token over the traced window's length, in %."""


def read(ctx):
    t = ctx.trace
    if not t or t["window_s"] <= 0:
        return None
    tokens = sum(s.live_rows for s in ctx.steps) * ctx.seq_len
    work = tokens * ctx.flops_per_token()
    return 100.0 * work / (t["window_s"] * ctx.chips * ctx.peak["bf16_flops_per_s"])
