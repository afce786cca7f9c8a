"""The window's live tokens times the FLOPs the model requires per token
(the configuration's work module: no recomputation, causal attention),
over the chips' bf16 peak (``peaks.json``), in %."""


def read(ctx):
    tokens = sum(s.live_rows for s in ctx.steps) * ctx.seq_len
    work = tokens * ctx.flops_per_token()
    return 100.0 * work / (ctx.window_s * ctx.chips * ctx.peak["bf16_flops_per_s"])
