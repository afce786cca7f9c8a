"""Device self time per step of the ops run under the name scope
``attention``: the q/k/v/o projections, RoPE and the attention core
(on the TPU the splash kernels), in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.scope_ms(ctx.trace, "attention")
