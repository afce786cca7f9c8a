"""Host time per step in the program's span ``train.dispatch``, the
dispatch of the compiled step, in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.span_ms(ctx.trace, "train.dispatch")
