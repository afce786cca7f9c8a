"""Pure-jnp oracles for the Pallas kernels.

Two tiers:
  * ``*_naive``   — maximally simple einsum forms (the ground truth used by
                    kernel sweep tests; O(S^2) memory).
  * ``*_blocked`` — numerically identical online-softmax / chunked-scan
                    formulations with O(S*block) memory. These are what the
                    models call on non-TPU backends and what the Pallas
                    kernels implement tile-by-tile on TPU.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.models.scan_util import layer_scan

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _gqa_expand(k: jnp.ndarray, num_q_heads: int) -> jnp.ndarray:
    """(B, S, Hkv, D) -> (B, S, Hq, D) by repeating KV heads."""
    b, s, hkv, d = k.shape
    if hkv == num_q_heads:
        return k
    group = num_q_heads // hkv
    return jnp.repeat(k, group, axis=2)


def attention_naive(
    q: jnp.ndarray,               # (B, Sq, Hq, D)
    k: jnp.ndarray,               # (B, Sk, Hkv, D)
    v: jnp.ndarray,               # (B, Sk, Hkv, D)
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_offset: int = 0,            # absolute position of q[0] (decode)
    kv_mask: Optional[jnp.ndarray] = None,   # (B, Sk) 1=valid
) -> jnp.ndarray:
    """O(Sq*Sk) oracle attention."""
    orig_dtype = q.dtype
    hq = q.shape[2]
    k = _gqa_expand(k, hq)
    v = _gqa_expand(v, hq)
    q32, k32, v32 = q.astype(jnp.float32), k.astype(jnp.float32), v.astype(jnp.float32)
    scale = 1.0 / jnp.sqrt(q.shape[-1]).astype(jnp.float32)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q32, k32) * scale
    sq, sk = q.shape[1], k.shape[1]
    q_pos = jnp.arange(sq) + q_offset
    k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), dtype=bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if sliding_window:
        mask &= q_pos[:, None] - k_pos[None, :] < sliding_window
    scores = jnp.where(mask[None, None], scores, NEG_INF)
    if kv_mask is not None:
        scores = jnp.where(kv_mask[:, None, None, :].astype(bool), scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v32)
    return out.astype(orig_dtype)


def attention_blocked(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    sliding_window: int = 0,
    q_offset: int = 0,
    kv_mask: Optional[jnp.ndarray] = None,
    block_k: int = 512,
) -> jnp.ndarray:
    """Online-softmax attention, scanned over KV blocks (O(Sq*block_k) mem).

    K/V stay in their storage dtype and are dynamic-sliced per block (no
    pre-stacked/pre-cast copy); GQA expansion happens per block. This is
    the algorithm the Pallas kernel implements tile-by-tile; it doubles as
    the scalable CPU/dry-run attention path. Values may be narrower than
    queries and keys (latent attention: q/k 192, v 128).
    """
    orig_dtype = q.dtype
    b, sq, hq, d = q.shape
    dv = v.shape[-1]
    sk, hkv = k.shape[1], k.shape[2]
    if sk % block_k:
        pad = block_k - sk % block_k
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        pad_mask = jnp.concatenate(
            [jnp.ones((b, sk)), jnp.zeros((b, pad))], axis=1)
        kv_mask = pad_mask if kv_mask is None else (
            jnp.concatenate([kv_mask.astype(jnp.float32),
                             jnp.zeros((b, pad))], axis=1))
        sk += pad
    nblocks = sk // block_k
    # scale folded into q up front: one small (B,Sq,H,D) multiply replaces
    # a (B,H,Sq,block_k) multiply per KV block (§Perf: score-chain bytes)
    scale = 1.0 / jnp.sqrt(jnp.asarray(d, jnp.float32))
    q32 = q.astype(jnp.float32) * scale
    q_pos = jnp.arange(sq) + q_offset

    def body(carry, blk):
        m, l, acc = carry
        start = blk * block_k
        kc = jax.lax.dynamic_slice_in_dim(k, start, block_k, axis=1)
        vc = jax.lax.dynamic_slice_in_dim(v, start, block_k, axis=1)
        kc = _gqa_expand(kc, hq).astype(jnp.float32)
        vc = _gqa_expand(vc, hq).astype(jnp.float32)
        k_pos = start + jnp.arange(block_k)
        s = jnp.einsum("bqhd,bkhd->bhqk", q32, kc)
        allow = jnp.ones((sq, block_k), dtype=bool)
        if causal:
            allow = allow & (q_pos[:, None] >= k_pos[None, :])
        if sliding_window:
            allow = allow & (q_pos[:, None] - k_pos[None, :] < sliding_window)
        allow = allow[None, None]
        if kv_mask is not None:
            maskc = jax.lax.dynamic_slice_in_dim(
                kv_mask.astype(bool), start, block_k, axis=1)
            allow = allow & maskc[:, None, None, :]
        s = jnp.where(allow, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum("bhqk,bkhd->bhqd", p, vc)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hq, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hq, sq), jnp.float32)
    acc0 = jnp.zeros((b, hq, sq, dv), jnp.float32)
    (m, l, acc), _ = layer_scan(body, (m0, l0, acc0), jnp.arange(nblocks))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(orig_dtype)


# ---------------------------------------------------------------------------
# Mamba2 SSD (state-space duality) chunked scan
# ---------------------------------------------------------------------------


def ssd_naive(
    x: jnp.ndarray,      # (B, S, H, P)
    dt: jnp.ndarray,     # (B, S, H)      softplus'd already
    A: jnp.ndarray,      # (H,)           negative
    B_mat: jnp.ndarray,  # (B, S, N)      shared across heads (ngroups=1)
    C_mat: jnp.ndarray,  # (B, S, N)
    D: jnp.ndarray,      # (H,)
    *,
    initial_state: Optional[jnp.ndarray] = None,  # (B, H, N, P)
) -> jnp.ndarray:
    """Sequential recurrence oracle: S_t = exp(dt_t A) S_{t-1} + dt_t B_t x_t."""
    b, s, h, p = x.shape
    n = B_mat.shape[-1]
    x32 = x.astype(jnp.float32)
    dt32 = dt.astype(jnp.float32)
    decay = jnp.exp(dt32 * A[None, None, :])                   # (B,S,H)
    state = (jnp.zeros((b, h, n, p), jnp.float32)
             if initial_state is None else initial_state.astype(jnp.float32))

    def step(state, t):
        d_t = decay[:, t]                                      # (B,H)
        dBx = jnp.einsum("bh,bn,bhp->bhnp", dt32[:, t], B_mat[:, t].astype(jnp.float32),
                         x32[:, t])
        state = state * d_t[:, :, None, None] + dBx
        y = jnp.einsum("bn,bhnp->bhp", C_mat[:, t].astype(jnp.float32), state)
        return state, y

    state, ys = jax.lax.scan(step, state, jnp.arange(s))
    y = ys.transpose(1, 0, 2, 3) + x32 * D[None, None, :, None]
    return y.astype(x.dtype), state


def ssd_chunked(
    x: jnp.ndarray,
    dt: jnp.ndarray,
    A: jnp.ndarray,
    B_mat: jnp.ndarray,
    C_mat: jnp.ndarray,
    D: jnp.ndarray,
    *,
    chunk: int = 256,
    initial_state: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Blocked SSD: intra-chunk quadratic form + inter-chunk recurrence.

    Identical math to ``ssd_naive`` (up to fp assoc); O(S*chunk) memory and
    matmul-dominated — the algorithm the Pallas kernel tiles.
    """
    b, s, h, p = x.shape
    n = B_mat.shape[-1]
    assert s % chunk == 0, f"seq {s} % chunk {chunk}"
    nc = s // chunk
    f32 = jnp.float32
    xc = x.astype(f32).reshape(b, nc, chunk, h, p)
    dtc = dt.astype(f32).reshape(b, nc, chunk, h)
    Bc = B_mat.astype(f32).reshape(b, nc, chunk, n)
    Cc = C_mat.astype(f32).reshape(b, nc, chunk, n)
    a = dtc * A[None, None, None, :]                 # (B,NC,Q,H) log-decays
    cum = jnp.cumsum(a, axis=2)                      # inclusive cumsum
    a_tot = cum[:, :, -1]                            # (B,NC,H) chunk total

    # --- intra-chunk (diagonal blocks) ---
    # L[i,j] = exp(cum_i - cum_j) for i >= j  (decay j+1..i applied)
    seg = cum[:, :, :, None, :] - cum[:, :, None, :, :]        # (B,NC,Q,Q,H)
    idx = jnp.arange(chunk)
    causal = (idx[:, None] >= idx[None, :])[None, None, :, :, None]
    L = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    cb = jnp.einsum("bcin,bcjn->bcij", Cc, Bc)                 # (B,NC,Q,Q)
    y_diag = jnp.einsum("bcij,bcijh,bcjh,bcjhp->bcihp",
                        cb, L, dtc, xc)

    # --- chunk states ---
    # state_c = sum_j exp(a_tot - cum_j) dt_j B_j x_j^T    (B,NC,H,N,P)
    decay_to_end = jnp.exp(a_tot[:, :, None, :] - cum)         # (B,NC,Q,H)
    states = jnp.einsum("bcqh,bcqh,bcqn,bcqhp->bchnp",
                        decay_to_end, dtc, Bc, xc)

    # --- inter-chunk recurrence ---
    init = (jnp.zeros((b, h, n, p), f32)
            if initial_state is None else initial_state.astype(f32))

    def chunk_step(carry, xs):
        st_in = carry
        st_c, atot_c = xs                                      # (B,H,N,P),(B,H)
        st_out = st_in * jnp.exp(atot_c)[:, :, None, None] + st_c
        return st_out, st_in                                   # emit state *before* chunk

    states_t = states.transpose(1, 0, 2, 3, 4)
    atot_t = a_tot.transpose(1, 0, 2)
    final_state, prev_states = layer_scan(chunk_step, init, (states_t, atot_t))
    prev_states = prev_states.transpose(1, 0, 2, 3, 4)         # (B,NC,H,N,P)

    # --- inter-chunk output: C_i exp(cum_i) S_prev ---
    decay_from_start = jnp.exp(cum)                            # (B,NC,Q,H)
    y_off = jnp.einsum("bcqn,bcqh,bchnp->bcqhp",
                       Cc, decay_from_start, prev_states)

    y = (y_diag + y_off).reshape(b, s, h, p) + x.astype(f32) * D[None, None, :, None]
    return y.astype(x.dtype), final_state


def ssd_decode_step(
    x: jnp.ndarray,      # (B, H, P) one token
    dt: jnp.ndarray,     # (B, H)
    A: jnp.ndarray,      # (H,)
    B_mat: jnp.ndarray,  # (B, N)
    C_mat: jnp.ndarray,  # (B, N)
    D: jnp.ndarray,      # (H,)
    state: jnp.ndarray,  # (B, H, N, P)
):
    f32 = jnp.float32
    decay = jnp.exp(dt.astype(f32) * A[None, :])               # (B,H)
    dBx = jnp.einsum("bh,bn,bhp->bhnp", dt.astype(f32),
                     B_mat.astype(f32), x.astype(f32))
    state = state.astype(f32) * decay[:, :, None, None] + dBx
    y = jnp.einsum("bn,bhnp->bhp", C_mat.astype(f32), state)
    y = y + x.astype(f32) * D[None, :, None]
    return y.astype(x.dtype), state
