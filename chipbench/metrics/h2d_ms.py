"""Host time per step in the program's span ``train.h2d``, the
host-to-device copies of the batch, in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.span_ms(ctx.trace, "train.h2d")
