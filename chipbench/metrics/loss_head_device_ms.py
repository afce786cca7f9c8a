"""Device self time per step of the ops run under the name scope
``loss_head``: the LM head over the vocabulary slice and the
cross-entropy, forward and backward, in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.scope_ms(ctx.trace, "loss_head")
