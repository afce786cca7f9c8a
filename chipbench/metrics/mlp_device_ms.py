"""Device self time per step of the ops run under the name scope ``mlp``:
the feed-forward block, forward and backward, in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.scope_ms(ctx.trace, "mlp")
