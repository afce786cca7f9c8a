"""Device self time per step of the ops run under the name scope
``optimizer``: the AdamW update and its addition to the parameters,
in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.scope_ms(ctx.trace, "optimizer")
