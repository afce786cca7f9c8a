"""Public kernel entry points used by the models.

Dispatch policy (``impl`` argument or ``REPRO_KERNEL_IMPL`` env):
  * ``blocked`` (default) — pure-jnp online-softmax / chunked-scan refs.
    Numerically identical to the Pallas kernels, lowers on any backend and
    under any SPMD sharding; this is what the dry-run and CPU training use.
  * ``pallas``  — the Pallas TPU kernels, in interpret mode on the CPU
    backend only. A call the kernel cannot serve (a ``kv_mask``, a
    ``q_offset``, an ``initial_state`` or a ragged chunk) raises rather
    than quietly running the reference.
  * ``naive``   — O(S^2) einsum oracle (tests only).

Models keep the (B, S, H, D) layout; this module adapts to kernel layouts.
"""
from __future__ import annotations

import os
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import ref as _ref
from repro.kernels import ssd_scan as _ssd


def _impl(override: Optional[str]) -> str:
    return override or os.environ.get("REPRO_KERNEL_IMPL", "blocked")


def _interpret() -> bool:
    """Interpret mode on the CPU backend, compiled kernels elsewhere."""
    return jax.default_backend() == "cpu"


def _unservable(kernel: str, why: str) -> NotImplementedError:
    return NotImplementedError(
        f"impl='pallas': the {kernel} kernel does not support {why}; "
        f"ask for impl='blocked'")


def attention(
    q: jnp.ndarray,               # (B, Sq, Hq, D)
    k: jnp.ndarray,               # (B, Sk, Hkv, D)
    v: jnp.ndarray,               # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_offset: int = 0,
    kv_mask: Optional[jnp.ndarray] = None,
    impl: Optional[str] = None,
    block_q: int = 128,
    block_k: int = 512,
) -> jnp.ndarray:
    """Multi-head (GQA) attention with causal / sliding-window masking."""
    impl = _impl(impl)
    if impl == "pallas":
        if kv_mask is not None or q_offset != 0:
            raise _unservable("flash_attention", "kv_mask or q_offset")
        qt = q.transpose(0, 2, 1, 3)
        kt = k.transpose(0, 2, 1, 3)
        vt = v.transpose(0, 2, 1, 3)
        out = _fa.flash_attention(
            qt, kt, vt, causal=causal, sliding_window=sliding_window,
            block_q=block_q, block_k=block_k, interpret=_interpret())
        return out.transpose(0, 2, 1, 3)
    if impl == "naive":
        return _ref.attention_naive(
            q, k, v, causal=causal, sliding_window=sliding_window,
            q_offset=q_offset, kv_mask=kv_mask)
    return _ref.attention_blocked(
        q, k, v, causal=causal, sliding_window=sliding_window,
        q_offset=q_offset, kv_mask=kv_mask, block_k=block_k)


def decode_attention(
    q: jnp.ndarray,               # (B, 1, Hq, D)
    k_cache: jnp.ndarray,         # (B, Sk, Hkv, D)
    v_cache: jnp.ndarray,
    *,
    q_offset,                     # scalar/traced absolute position
    kv_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Single-token attention over a (possibly ring-buffered) KV cache.

    Pure einsum: with one query the op is memory-bound and XLA's sharded
    softmax (partial max/sum + all-reduce over a sequence-sharded cache)
    is already optimal — no kernel needed.
    """
    b, sk, hkv, d = k_cache.shape
    hq = q.shape[2]
    g = hq // hkv
    q32 = q.astype(jnp.float32).reshape(b, hkv, g, d)
    k32 = k_cache.astype(jnp.float32)
    v32 = v_cache.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    s = jnp.einsum("bhgd,bkhd->bhgk", q32, k32) * scale
    k_pos = jnp.arange(sk)
    allow = k_pos[None, :] <= jnp.asarray(q_offset).reshape(-1, 1)
    if kv_mask is not None:
        allow = allow & kv_mask.astype(bool)
    s = jnp.where(allow[:, None, None, :], s, _ref.NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgk,bkhd->bhgd", p, v32)
    return out.reshape(b, 1, hq, d).astype(q.dtype)


def ssd(
    x: jnp.ndarray,       # (B, S, H, P)
    dt: jnp.ndarray,      # (B, S, H)
    A: jnp.ndarray,       # (H,)
    B_mat: jnp.ndarray,   # (B, S, N)
    C_mat: jnp.ndarray,   # (B, S, N)
    D: jnp.ndarray,       # (H,)
    *,
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,
    impl: Optional[str] = None,
):
    """Mamba2 SSD over a sequence; returns (y, final_state)."""
    impl = _impl(impl)
    s = x.shape[1]
    chunk = min(chunk, s)
    if impl == "pallas":
        if initial_state is not None or s % chunk:
            raise _unservable("ssd_scan", "initial_state or a sequence "
                              "that is not a multiple of the chunk")
        xt = x.transpose(0, 2, 1, 3)
        dtt = dt.transpose(0, 2, 1)
        y = _ssd.ssd_scan(xt, dtt, A, B_mat, C_mat, D,
                          chunk=chunk, interpret=_interpret())
        return y.transpose(0, 2, 1, 3), None
    if impl == "naive":
        return _ref.ssd_naive(x, dt, A, B_mat, C_mat, D,
                              initial_state=initial_state)
    if s % chunk:
        pad = chunk - s % chunk
        xp = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dtp = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        Bp = jnp.pad(B_mat, ((0, 0), (0, pad), (0, 0)))
        Cp = jnp.pad(C_mat, ((0, 0), (0, pad), (0, 0)))
        y, st = _ref.ssd_chunked(xp, dtp, A, Bp, Cp, D, chunk=chunk,
                                 initial_state=initial_state)
        return y[:, :s], st
    return _ref.ssd_chunked(x, dt, A, B_mat, C_mat, D, chunk=chunk,
                            initial_state=initial_state)


ssd_decode_step = _ref.ssd_decode_step
