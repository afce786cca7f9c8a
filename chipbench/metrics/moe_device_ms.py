"""Device self time per step of the ops run under the name scope ``moe``:
the expert layers' router, held experts' grouped products with their
sort and permutations, shared experts and the routing bias's update,
forward and backward, in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.scope_ms(ctx.trace, "moe")
