"""Pallas TPU kernel for the Mamba2 SSD chunked scan.

Grid ``(B, H, S/Q)`` — the chunk axis iterates sequentially on TPU, so the
inter-chunk SSM state (N, P) lives in VMEM scratch. Per chunk the kernel
does the SSD blocked algorithm (arXiv:2405.21060):

  intra:  y_d = ((C B^T) ⊙ L ⊙ dt) x           (Q,Q)x(Q,P) matmuls — MXU
  carry:  state' = exp(a_tot) state + (decay_to_end ⊙ dt ⊙ B)^T x
  inter:  y_o = (C ⊙ decay_from_start) state

Layouts (ops.py adapts): x (B, H, S, P), dt (B, H, S), B/C (B, S, N),
A (H,), D (H,). dt enters the kernel as (B, H, 1, S), so its block is a
lane-dense (1, Q) row; A and D are whole f32 vectors in SMEM. Q=chunk
(default 256), N≤256, P=64 keep the working set (a few (Q, Q) f32
temporaries + 2*Q*N + Q*P + N*P floats ≈ 1.5 MB) well inside VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref,
                y_ref, state_ref, *, chunk: int):
    h = pl.program_id(1)
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    x = x_ref[0, 0].astype(jnp.float32)          # (Q, P)
    dt_row = dt_ref[0, 0].astype(jnp.float32)    # (1, Q)
    B = b_ref[0].astype(jnp.float32)             # (Q, N)
    C = c_ref[0].astype(jnp.float32)             # (Q, N)
    A = a_ref[h]                                 # scalars from SMEM
    D = d_ref[h]

    # the prefix sums as masked row/column reductions over (Q, Q): Mosaic
    # has no cumsum, and the column form of dt needs no transpose
    ii = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    dt_col = jnp.sum(jnp.where(ii == jj, dt_row, 0.0), axis=1,
                     keepdims=True)              # (Q, 1)
    a_row = dt_row * A                           # log-decays
    a_col = dt_col * A
    cum_col = jnp.sum(jnp.where(jj <= ii, a_row, 0.0), axis=1,
                      keepdims=True)             # (Q, 1) inclusive
    cum_row = jnp.sum(jnp.where(ii <= jj, a_col, 0.0), axis=0,
                      keepdims=True)             # (1, Q) inclusive
    a_tot = jnp.sum(a_row, axis=1, keepdims=True)  # (1, 1)

    # intra-chunk
    seg = cum_col - cum_row                      # (Q, Q)
    L = jnp.where(ii >= jj, jnp.exp(seg), 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)   # (Q,Q)
    w = cb * L * dt_row
    y_d = jax.lax.dot_general(w, x, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q,P)

    # inter-chunk (uses state BEFORE this chunk)
    st = state_ref[...]                          # (N, P)
    y_o = jax.lax.dot_general(C * jnp.exp(cum_col), st,
                              (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # (Q,P)

    # state update
    dte = jnp.exp(a_tot - cum_col) * dt_col      # (Q, 1)
    st_c = jax.lax.dot_general(B * dte, x, (((0,), (0,)), ((), ())),
                               preferred_element_type=jnp.float32)  # (N,P)
    state_ref[...] = st * jnp.exp(a_tot) + st_c

    y_ref[0, 0] = (y_d + y_o + x * D).astype(y_ref.dtype)


def ssd_scan(
    x: jnp.ndarray,       # (B, H, S, P)
    dt: jnp.ndarray,      # (B, H, S)
    A: jnp.ndarray,       # (H,)
    B_mat: jnp.ndarray,   # (B, S, N)
    C_mat: jnp.ndarray,   # (B, S, N)
    D: jnp.ndarray,       # (H,)
    *,
    chunk: int = 256,
    interpret: bool = True,
) -> jnp.ndarray:
    b, h, s, p = x.shape
    n = B_mat.shape[-1]
    chunk = min(chunk, s)
    assert s % chunk == 0, (s, chunk)
    nc = s // chunk
    kernel = functools.partial(_ssd_kernel, chunk=chunk)
    y = pl.pallas_call(
        kernel,
        grid=(b, h, nc),
        in_specs=[
            pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0)),
            pl.BlockSpec((1, 1, 1, chunk), lambda b_, h_, c: (b_, h_, 0, c)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0)),
            pl.BlockSpec((1, chunk, n), lambda b_, h_, c: (b_, c, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((1, 1, chunk, p), lambda b_, h_, c: (b_, h_, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((n, p), jnp.float32)],
        interpret=interpret,
    )(x, dt.reshape(b, h, 1, s), B_mat, C_mat,
      A.astype(jnp.float32), D.astype(jnp.float32))
    return y
