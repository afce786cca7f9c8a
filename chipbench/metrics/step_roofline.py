"""The least time for a step's required work (the larger of FLOPs over
peak and bytes over HBM bandwidth, from the configuration's work module,
live rows only) over the device-busy time per step, in %."""


def read(ctx):
    t = ctx.trace
    if not t or t["busy_s"] <= 0:
        return None
    live_tokens = sum(s.live_rows for s in ctx.steps) * ctx.seq_len / len(ctx.steps)
    least, _ = ctx.least_step_seconds(live_tokens)
    return 100.0 * least / (t["busy_s"] / t["n_steps"])
