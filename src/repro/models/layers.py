"""Shared model layers (functional, pytree params).

Naming contract (shardings.py keys on leaf names):
  attention: wq (E, Hq*D), wk/wv (E, Hkv*D), wo (Hq*D, E), bq/bk/bv
  mlp:       w_gate/w_up (E, F), w_down (F, E)
  moe:       router (E, X), moe_gate/moe_up (X, E, F), moe_down (X, F, E)
  norms:     scale (E,)
  embeds:    embedding (V, E), lm_head (E, V)
Stacked layers prepend an L dim to every leaf.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import shardings as sh

Params = Dict[str, Any]


def compute_dtype(cfg: ArchConfig):
    return jnp.bfloat16 if cfg.dtype == "bfloat16" else jnp.float32


def maybe_checkpoint(body, remat):
    """Remat policy dial (EXPERIMENTS.md §Perf):
      True/"full" — recompute everything in bwd (min HBM, max bytes);
      "hot"       — save the named block outputs (attn_out/ffn_out/...):
                    the backward recomputes attention scores ONCE (for its
                    own grads) instead of twice, at ~2 small (B,S,E)
                    saves per layer;
      "dots"      — save matmul outputs w/o batch dims;
      False/"none"— store all activations (max HBM, min bytes)."""
    if remat in (False, "none", None):
        return body
    if remat == "hot":
        return jax.checkpoint(
            body, policy=jax.checkpoint_policies.save_only_these_names(
                "attn_out", "ffn_out", "ssm_out"))
    if remat == "dots":
        return jax.checkpoint(
            body,
            policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)
    return jax.checkpoint(body)


def named(x: jnp.ndarray, name: str) -> jnp.ndarray:
    """checkpoint_name marker for the "hot" remat policy."""
    from jax.ad_checkpoint import checkpoint_name
    return checkpoint_name(x, name)


def padded_vocab(cfg: ArchConfig, multiple: int = 256) -> int:
    v = cfg.vocab_size
    return ((v + multiple - 1) // multiple) * multiple


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def _dense_init(key, fan_in: int, shape, scale: float = 1.0):
    std = scale / (fan_in ** 0.5)
    return (jax.random.normal(key, shape, jnp.float32) * std)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, eps: float) -> jnp.ndarray:
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    out = x32 * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)).astype(x.dtype)


def init_norm(d: int) -> Params:
    return {"scale": jnp.ones((d,), jnp.float32)}


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_tables(positions: jnp.ndarray, head_dim: int, theta: float):
    """positions (...,) -> cos/sin (..., head_dim/2) in f32."""
    half = head_dim // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray):
    """x (B, S, H, D); cos/sin (S, D/2) or (B, S, D/2)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    if cos.ndim == 2:
        cos_, sin_ = cos[None, :, None, :], sin[None, :, None, :]
    else:
        cos_, sin_ = cos[:, :, None, :], sin[:, :, None, :]
    x32_1, x32_2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    o1 = x32_1 * cos_ - x32_2 * sin_
    o2 = x32_2 * cos_ + x32_1 * sin_
    return jnp.concatenate([o1, o2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention block
# ---------------------------------------------------------------------------


def init_attention(key, cfg: ArchConfig, out_scale: float = 1.0) -> Params:
    E, hq, hkv, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": _dense_init(ks[0], E, (E, hq * hd)),
        "wk": _dense_init(ks[1], E, (E, hkv * hd)),
        "wv": _dense_init(ks[2], E, (E, hkv * hd)),
        "wo": _dense_init(ks[3], hq * hd, (hq * hd, E), scale=out_scale),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * hd,), jnp.float32)
        p["bk"] = jnp.zeros((hkv * hd,), jnp.float32)
        p["bv"] = jnp.zeros((hkv * hd,), jnp.float32)
    return p


def _project_qkv(p: Params, cfg: ArchConfig, x, kv_x):
    dt = x.dtype
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = x @ p["wq"].astype(dt)
    k = kv_x @ p["wk"].astype(dt)
    v = kv_x @ p["wv"].astype(dt)
    if "bq" in p:
        q = q + p["bq"].astype(dt)
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    b, sq = x.shape[:2]
    sk = kv_x.shape[1]
    q = q.reshape(b, sq, hq, hd)
    k = k.reshape(b, sk, hkv, hd)
    v = v.reshape(b, sk, hkv, hd)
    return q, k, v


def attention_block(
    p: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,                      # (B, S, E)
    *,
    positions: Optional[jnp.ndarray] = None,   # (S,) or (B, S)
    causal: bool = True,
    use_rope: bool = True,
    cross_x: Optional[jnp.ndarray] = None,     # (B, Sk, E) for cross-attn
    kv_mask: Optional[jnp.ndarray] = None,
) -> jnp.ndarray:
    """Full-sequence attention (train / prefill), under the ``attention``
    name scope."""
    with jax.named_scope("attention"):
        kv_src = cross_x if cross_x is not None else x
        q, k, v = _project_qkv(p, cfg, x, kv_src)
        if use_rope and cross_x is None:
            if positions is None:
                positions = jnp.arange(x.shape[1])
            cos, sin = rope_tables(positions, cfg.resolved_head_dim, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
        q = sh.constrain_act(q, "heads")
        k = sh.constrain_act(k, "heads")
        v = sh.constrain_act(v, "heads")
        out = ops.attention(
            q, k, v, causal=causal and cross_x is None,
            sliding_window=cfg.sliding_window if cross_x is None else 0,
            kv_mask=kv_mask, cross=cross_x is not None)
        out = named(out, "attn_out")
        b, s = x.shape[:2]
        out = out.reshape(b, s, -1) @ p["wo"].astype(x.dtype)
        return sh.constrain_act(out, "res")


def attention_decode(
    p: Params,
    cfg: ArchConfig,
    x: jnp.ndarray,                      # (B, 1, E)
    k_cache: jnp.ndarray,                # (B, Smax, Hkv, D)
    v_cache: jnp.ndarray,
    pos: jnp.ndarray,                    # (B,) absolute position of new token
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-token attention; writes the new KV at ``pos`` (ring for SWA)."""
    q, k, v = _project_qkv(p, cfg, x, x)
    cos, sin = rope_tables(pos[:, None], cfg.resolved_head_dim, cfg.rope_theta)
    q = apply_rope(q, cos, sin)
    k = apply_rope(k, cos, sin)
    smax = k_cache.shape[1]
    slot = pos % smax if cfg.sliding_window else jnp.minimum(pos, smax - 1)
    bidx = jnp.arange(x.shape[0])
    k_cache = k_cache.at[bidx, slot].set(k[:, 0].astype(k_cache.dtype))
    v_cache = v_cache.at[bidx, slot].set(v[:, 0].astype(v_cache.dtype))
    if cfg.sliding_window:
        # ring buffer: every slot written within the last `smax` steps is live
        slot_pos = jnp.arange(smax)[None, :]
        age = (slot[:, None] - slot_pos) % smax
        kv_mask = age < jnp.minimum(pos + 1, smax)[:, None]
        out = ops.decode_attention(q, k_cache, v_cache,
                                   q_offset=pos[:, None] * 0 + jnp.iinfo(jnp.int32).max // 2,
                                   kv_mask=kv_mask)
    else:
        out = ops.decode_attention(q, k_cache, v_cache, q_offset=pos)
    out = out.reshape(x.shape[0], 1, -1) @ p["wo"].astype(x.dtype)
    return out, k_cache, v_cache


def cross_attention_decode(p, cfg, x, ck_cache, cv_cache, enc_mask=None):
    """Decode-time cross attention over cached encoder K/V."""
    dt = x.dtype
    hq, hkv, hd = cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim
    q = (x @ p["wq"].astype(dt))
    if "bq" in p:
        q = q + p["bq"].astype(dt)
    q = q.reshape(x.shape[0], 1, hq, hd)
    smax = ck_cache.shape[1]
    out = ops.decode_attention(q, ck_cache, cv_cache,
                               q_offset=jnp.full((x.shape[0],), smax - 1),
                               kv_mask=enc_mask)
    return out.reshape(x.shape[0], 1, -1) @ p["wo"].astype(dt)


def cross_kv(p: Params, cfg: ArchConfig, enc: jnp.ndarray):
    """Project encoder states to this layer's cross K/V (cached at prefill)."""
    dt = enc.dtype
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    k = enc @ p["wk"].astype(dt)
    v = enc @ p["wv"].astype(dt)
    if "bk" in p:
        k = k + p["bk"].astype(dt)
        v = v + p["bv"].astype(dt)
    b, s = enc.shape[:2]
    return k.reshape(b, s, hkv, hd), v.reshape(b, s, hkv, hd)


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def init_mlp(key, cfg: ArchConfig, d_ff: Optional[int] = None,
             out_scale: float = 1.0) -> Params:
    E = cfg.d_model
    F = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    if cfg.activation == "swiglu":
        return {
            "w_gate": _dense_init(ks[0], E, (E, F)),
            "w_up": _dense_init(ks[1], E, (E, F)),
            "w_down": _dense_init(ks[2], F, (F, E), scale=out_scale),
        }
    return {
        "w_up": _dense_init(ks[1], E, (E, F)),
        "w_down": _dense_init(ks[2], F, (F, E), scale=out_scale),
    }


def mlp_block(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    """The feed-forward block, under the ``mlp`` name scope."""
    with jax.named_scope("mlp"):
        dt = x.dtype
        up = x @ p["w_up"].astype(dt)
        if "w_gate" in p:
            h = jax.nn.silu(x @ p["w_gate"].astype(dt)) * up
        else:
            h = jax.nn.gelu(up)
        h = sh.constrain_act(h, "ff")
        out = h @ p["w_down"].astype(dt)
        return named(sh.constrain_act(out, "res"), "ffn_out")


# ---------------------------------------------------------------------------
# embeddings / logits
# ---------------------------------------------------------------------------


def init_embedding(key, cfg: ArchConfig) -> Params:
    V = padded_vocab(cfg)
    p = {"embedding": jax.random.normal(key, (V, cfg.d_model), jnp.float32) * 0.02}
    if not cfg.tie_embeddings:
        p["lm_head"] = _dense_init(jax.random.fold_in(key, 1), cfg.d_model,
                                   (cfg.d_model, V))
    return p


def embed(p: Params, cfg: ArchConfig, tokens: jnp.ndarray) -> jnp.ndarray:
    x = jnp.take(p["embedding"].astype(compute_dtype(cfg)), tokens, axis=0)
    return sh.constrain_act(x, "res")


def logits(p: Params, cfg: ArchConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.tie_embeddings:
        out = x @ p["embedding"].T.astype(x.dtype)
    else:
        out = x @ p["lm_head"].astype(x.dtype)
    return sh.constrain_act(out, "logits")
