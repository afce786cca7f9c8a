"""The least time for the held experts' grouped products (the larger of
their FLOPs over the bf16 peak and their bytes over HBM bandwidth, from
the work module's ``gmm_flops`` and ``gmm_bytes`` at the window's routed
rows, the program's counter ``moe.rows.held`` per step) over the device
self time per step under the name scope ``experts``, in %."""
from chipbench import trace_reduce


def read(ctx):
    t = ctx.trace
    rows = (t or {}).get("counters", {}).get("moe.rows.held")
    device_ms = trace_reduce.scope_ms(t, "experts")
    work = ctx.work
    if not rows or not device_ms or not hasattr(work, "gmm_flops"):
        return None
    per_step = rows / len(ctx.steps)
    least = max(work.gmm_flops(ctx.model, per_step) / ctx.peak["bf16_flops_per_s"],
                work.gmm_bytes(ctx.model, per_step) / ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / (device_ms / 1e3)
