"""Device self time per step of the ops run under the name scope ``mla``:
latent attention's projections, latent norm, RoPE and its attention core
(on the TPU the splash kernels at q/k 192, v 128), forward and backward,
in ms."""
from chipbench import trace_reduce


def read(ctx):
    return trace_reduce.scope_ms(ctx.trace, "mla")
