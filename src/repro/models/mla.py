"""Multi-head latent attention (DeepSeek-V2/V3, as Moonlight runs it).

Per token x (E):

  q            = x W_q                     H heads of nope + rope
  [c, k_r]     = x W_kv_a                  latent (kv_lora_rank) + one rotary key
  c            = RMSNorm(c)
  [k_n, v]     = c W_kv_b                  H heads of nope + v
  q_r, k_r     = RoPE(q_r), RoPE(k_r)      k_r shared by every head
  k            = [k_n, k_r],  q = [q_n, q_r]
  o            = softmax(q k^T / sqrt(nope + rope), causal) v
  out          = o W_o

RoPE is the rotate-half form of ``layers.apply_rope``: DeepSeek's code
rotates interleaved pairs, which differs only by a fixed permutation of
W_q's and W_kv_a's rotary columns. Training runs under the ``attention``
and ``mla`` name scopes, the core through ``ops.attention`` (q/k 192 and
v 128 on the splash kernel). Decoding keeps a latent cache: c and the
rotated k_r of every position, ``kv_lora_rank + rope`` numbers a token,
and decompresses keys and values from it each step.
"""
from __future__ import annotations

from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import layers as L

Params = Dict[str, jnp.ndarray]


def init_mla(key, cfg: ArchConfig, out_scale: float = 1.0) -> Params:
    a, E, H = cfg.mla, cfg.d_model, cfg.num_heads
    ks = jax.random.split(key, 4)
    r = a.kv_lora_rank
    return {
        "wq": L._dense_init(ks[0], E, (E, H * a.qk_head_dim)),
        "wkv_a": L._dense_init(ks[1], E, (E, r + a.qk_rope_head_dim)),
        "kv_norm": L.init_norm(r),
        "wkv_b": L._dense_init(ks[2], r, (r, H * (a.qk_nope_head_dim
                                                  + a.v_head_dim))),
        "wo": L._dense_init(ks[3], H * a.v_head_dim, (H * a.v_head_dim, E),
                            scale=out_scale),
    }


def _queries(p: Params, cfg: ArchConfig, x, cos, sin):
    a = cfg.mla
    b, s = x.shape[:2]
    q = (x @ p["wq"].astype(x.dtype)).reshape(b, s, cfg.num_heads,
                                              a.qk_head_dim)
    q_n, q_r = q[..., :a.qk_nope_head_dim], q[..., a.qk_nope_head_dim:]
    return q_n, L.apply_rope(q_r, cos, sin)


def _latent(p: Params, cfg: ArchConfig, x, cos, sin):
    """(c, k_r): the normed latent (B, S, r) and the rotated shared key
    (B, S, rope)."""
    r = cfg.mla.kv_lora_rank
    kv = x @ p["wkv_a"].astype(x.dtype)
    c = L.rmsnorm(kv[..., :r], p["kv_norm"]["scale"], cfg.norm_eps)
    k_r = L.apply_rope(kv[..., None, r:], cos, sin)[..., 0, :]
    return c, k_r


def _decompress(p: Params, cfg: ArchConfig, c):
    """(k_n, v) of every head from the latent."""
    a = cfg.mla
    b, s = c.shape[:2]
    kv = (c @ p["wkv_b"].astype(c.dtype)).reshape(
        b, s, cfg.num_heads, a.qk_nope_head_dim + a.v_head_dim)
    return kv[..., :a.qk_nope_head_dim], kv[..., a.qk_nope_head_dim:]


def mla_block(p: Params, cfg: ArchConfig, x: jnp.ndarray,
              positions: jnp.ndarray) -> jnp.ndarray:
    """Causal latent self-attention over a full sequence, x (B, S, E)."""
    with jax.named_scope("attention"), jax.named_scope("mla"):
        b, s = x.shape[:2]
        cos, sin = L.rope_tables(positions, cfg.mla.qk_rope_head_dim,
                                 cfg.rope_theta)
        q_n, q_r = _queries(p, cfg, x, cos, sin)
        c, k_r = _latent(p, cfg, x, cos, sin)
        k_n, v = _decompress(p, cfg, c)
        q = jnp.concatenate([q_n, q_r], axis=-1)
        k = jnp.concatenate(
            [k_n, jnp.broadcast_to(k_r[:, :, None, :], k_n.shape[:3]
                                   + k_r.shape[-1:])], axis=-1)
        out = ops.attention(q, k, v, causal=True)
        out = L.named(out, "attn_out")
        return out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)


def init_latent_cache(cfg: ArchConfig, layers: int, batch: int, max_len: int,
                      dtype) -> Dict[str, jnp.ndarray]:
    a = cfg.mla
    return {"c": jnp.zeros((layers, batch, max_len, a.kv_lora_rank), dtype),
            "kr": jnp.zeros((layers, batch, max_len, a.qk_rope_head_dim),
                            dtype)}


def mla_decode(p: Params, cfg: ArchConfig, x: jnp.ndarray,
               c_cache: jnp.ndarray, kr_cache: jnp.ndarray, pos: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One token x (B, 1, E) at ``pos`` (B,): writes its latent and rotary
    key into the caches (B, Smax, ·) and attends over positions 0..pos."""
    a = cfg.mla
    bsz = x.shape[0]
    cos, sin = L.rope_tables(pos[:, None], a.qk_rope_head_dim, cfg.rope_theta)
    q_n, q_r = _queries(p, cfg, x, cos, sin)
    c, k_r = _latent(p, cfg, x, cos, sin)
    smax = c_cache.shape[1]
    slot = jnp.minimum(pos, smax - 1)
    bidx = jnp.arange(bsz)
    c_cache = c_cache.at[bidx, slot].set(c[:, 0].astype(c_cache.dtype))
    kr_cache = kr_cache.at[bidx, slot].set(k_r[:, 0].astype(kr_cache.dtype))
    k_n, v = _decompress(p, cfg, c_cache.astype(x.dtype))
    f32 = jnp.float32
    scores = (jnp.einsum("bqhd,bkhd->bhqk", q_n.astype(f32), k_n.astype(f32))
              + jnp.einsum("bqhd,bkd->bhqk", q_r.astype(f32),
                           kr_cache.astype(f32)))
    scores = scores / jnp.sqrt(jnp.asarray(a.qk_head_dim, f32))
    allow = jnp.arange(smax)[None, :] <= pos[:, None]
    scores = jnp.where(allow[:, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v.astype(f32)).astype(x.dtype)
    out = out.reshape(bsz, 1, -1) @ p["wo"].astype(x.dtype)
    return out, c_cache, kr_cache
