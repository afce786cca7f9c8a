"""Decoder-only transformer family: dense, MoE, and VLM (cross-attn) LMs.

Layers are weight-stacked and driven by ``lax.scan`` (small HLO, fast
compiles at 30-48 layers). VLM cross-attention layers split the stack into
segments: scan k dense layers, apply one cross block, repeat. An expert
stack with leading dense layers (``moe.dense_prefix``) keeps those apart
(``dense_layers``) and scans them first. Attention is latent (models/mla.py)
where the config has an ``mla`` block.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.scan_util import layer_scan
from repro.models import layers as L
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models import shardings as sh

Params = Dict[str, Any]


def _stack(trees):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *trees)


def _slice(tree, a: int, b: int):
    return jax.tree.map(lambda x: x[a:b], tree)


def _ffn(lp: Params, cfg: ArchConfig, x, live=None):
    """(y, aux loss, routing stats) of a layer's feed-forward block;
    ``live`` is the batch's row mask."""
    if "mlp" in lp:
        return L.mlp_block(lp["mlp"], cfg, x), jnp.zeros((), jnp.float32), {}
    if cfg.moe.router == "noaux_tc":
        return M.held_experts_block(lp["moe"], cfg, x, live)
    impl = sh.get_moe_impl()
    if x.shape[1] > 1 and impl != "dense":
        from repro.models import moe_ep
        if impl == "ep_a2a" and moe_ep.ep_applicable(cfg, sh.get_mesh()):
            return (*moe_ep.moe_block_ep(lp["moe"], cfg, x), {})
        if impl == "fs" and moe_ep.fs_applicable(cfg, sh.get_mesh()):
            return (*moe_ep.moe_block_fs(lp["moe"], cfg, x), {})
    return (*M.moe_block(lp["moe"], cfg, x), {})


def _attention(ap: Params, cfg: ArchConfig, x, positions):
    if cfg.mla is not None:
        return MLA.mla_block(ap, cfg, x, positions)
    return L.attention_block(ap, cfg, x, positions=positions)


def _dense_prefix(cfg: ArchConfig) -> int:
    return cfg.moe.dense_prefix if cfg.moe is not None else 0


def init(key, cfg: ArchConfig) -> Params:
    ks = jax.random.split(key, 4)
    out_scale = 1.0 / (2 * cfg.num_layers) ** 0.5

    def one(k, dense):
        k1, k2 = jax.random.split(k)
        p = {"norm1": L.init_norm(cfg.d_model),
             "attn": (MLA.init_mla(k1, cfg, out_scale) if cfg.mla is not None
                      else L.init_attention(k1, cfg, out_scale)),
             "norm2": L.init_norm(cfg.d_model)}
        if dense:
            p["mlp"] = L.init_mlp(k2, cfg, out_scale=out_scale)
        elif cfg.moe.router == "noaux_tc":
            p["moe"] = M.init_held_experts(k2, cfg, out_scale)
        else:
            p["moe"] = M.init_moe(k2, cfg, out_scale)
        return p

    keys = jax.random.split(ks[1], cfg.num_layers)
    n_dense = _dense_prefix(cfg)
    layers = _stack([one(k, cfg.moe is None) for k in keys[n_dense:]])
    params = {"embed": L.init_embedding(ks[0], cfg), "layers": layers,
              "final_norm": L.init_norm(cfg.d_model)}
    if n_dense:
        params["dense_layers"] = _stack([one(k, True) for k in keys[:n_dense]])
    if cfg.cross_attn_every:
        n_cross = cfg.num_layers // cfg.cross_attn_every

        def one_cross(k):
            k1, k2 = jax.random.split(k)
            return {"norm1": L.init_norm(cfg.d_model),
                    "attn": L.init_attention(k1, cfg, out_scale),
                    "gate_attn": jnp.zeros((), jnp.float32),
                    "norm2": L.init_norm(cfg.d_model),
                    "mlp": L.init_mlp(k2, cfg, out_scale=out_scale),
                    "gate_mlp": jnp.zeros((), jnp.float32)}

        params["cross"] = _stack(
            [one_cross(k) for k in jax.random.split(ks[2], n_cross)])
    return params


def _layer_body(cfg: ArchConfig, positions, live=None):
    def body(carry, lp):
        x, aux = carry
        h = _attention(lp["attn"], cfg,
                       L.rmsnorm(x, lp["norm1"]["scale"], cfg.norm_eps),
                       positions)
        x = x + h
        h2, a, stats = _ffn(
            lp, cfg, L.rmsnorm(x, lp["norm2"]["scale"], cfg.norm_eps), live)
        return (x + h2, aux + a), stats
    return body


def _cross_block(cp: Params, cfg: ArchConfig, x, img, decode_cache=None):
    """llama-3.2-vision style gated cross-attn block."""
    if decode_cache is None:
        h = L.attention_block(cp["attn"], cfg,
                              L.rmsnorm(x, cp["norm1"]["scale"], cfg.norm_eps),
                              cross_x=img, use_rope=False)
    else:
        ck, cv = decode_cache
        h = L.cross_attention_decode(
            cp["attn"], cfg,
            L.rmsnorm(x, cp["norm1"]["scale"], cfg.norm_eps), ck, cv)
    x = x + jnp.tanh(cp["gate_attn"]).astype(x.dtype) * h
    h2 = L.mlp_block(cp["mlp"], cfg,
                     L.rmsnorm(x, cp["norm2"]["scale"], cfg.norm_eps))
    return x + jnp.tanh(cp["gate_mlp"]).astype(x.dtype) * h2


def forward(params: Params, cfg: ArchConfig, batch: Dict[str, Any],
            remat: bool = True, return_hidden: bool = False,
            with_stats: bool = False):
    """(logits or hidden, aux loss); with ``with_stats`` also the expert
    layers' routing stats, stacked by layer ({} where none route)."""
    tokens = batch["tokens"]
    x = L.embed(params["embed"], cfg, tokens)
    positions = jnp.arange(tokens.shape[1])
    body = L.maybe_checkpoint(
        _layer_body(cfg, positions, batch.get("sample_mask")), remat)
    aux0 = jnp.zeros((), jnp.float32)
    stats: Dict[str, Any] = {}
    if "dense_layers" in params:
        (x, aux0), _ = layer_scan(body, (x, aux0), params["dense_layers"])
    if cfg.cross_attn_every:
        img = batch["img_embeds"].astype(x.dtype)
        seg = cfg.cross_attn_every
        n_cross = cfg.num_layers // seg
        carry = (x, aux0)
        for i in range(n_cross):
            carry, _ = layer_scan(body, carry,
                                    _slice(params["layers"], i * seg, (i + 1) * seg))
            x, aux = carry
            cp = jax.tree.map(lambda a: a[i], params["cross"])
            x = _cross_block(cp, cfg, x, img)
            carry = (x, aux)
        rem = cfg.num_layers - n_cross * seg
        if rem:
            carry, _ = layer_scan(body, carry,
                                    _slice(params["layers"], n_cross * seg,
                                           cfg.num_layers))
        x, aux = carry
    else:
        (x, aux), stats = layer_scan(body, (x, aux0), params["layers"])
    x = L.rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    out = x if return_hidden else L.logits(params["embed"], cfg, x)
    return (out, aux, stats) if with_stats else (out, aux)


# ---------------------------------------------------------------------------
# serving
# ---------------------------------------------------------------------------


def init_cache(params: Params, cfg: ArchConfig, batch: int, max_len: int,
               dtype, aux: Optional[Dict[str, Any]] = None) -> Params:
    smax = min(max_len, cfg.sliding_window) if cfg.sliding_window else max_len
    hkv, hd = cfg.num_kv_heads, cfg.resolved_head_dim
    if cfg.mla is not None:
        cache = MLA.init_latent_cache(cfg, cfg.num_layers, batch, smax, dtype)
    else:
        cache = {
            "k": jnp.zeros((cfg.num_layers, batch, smax, hkv, hd), dtype),
            "v": jnp.zeros((cfg.num_layers, batch, smax, hkv, hd), dtype),
        }
    cache["pos"] = jnp.zeros((batch,), jnp.int32)
    if cfg.cross_attn_every:
        img = aux["img_embeds"].astype(dtype)
        ck, cv = jax.vmap(
            lambda cp: L.cross_kv(cp["attn"], cfg, img))(params["cross"])
        cache["ck"], cache["cv"] = ck, cv
    return cache


def decode_step(params: Params, cfg: ArchConfig, cache: Params,
                tokens: jnp.ndarray, aux: Optional[Dict] = None
                ) -> Tuple[jnp.ndarray, Params]:
    """tokens (B,1) -> logits (B,1,V); advances the KV cache (the latent
    cache under latent attention) one position."""
    x = L.embed(params["embed"], cfg, tokens)
    pos = cache["pos"]
    ka, kb = ("c", "kr") if cfg.mla is not None else ("k", "v")
    attend = MLA.mla_decode if cfg.mla is not None else L.attention_decode

    def body(x, scan_in):
        lp, kc, vc = scan_in
        h, kc, vc = attend(
            lp["attn"], cfg,
            L.rmsnorm(x, lp["norm1"]["scale"], cfg.norm_eps), kc, vc, pos)
        x = x + h
        h2, _, _ = _ffn(lp, cfg,
                        L.rmsnorm(x, lp["norm2"]["scale"], cfg.norm_eps))
        return x + h2, (kc, vc)

    if cfg.cross_attn_every:
        seg = cfg.cross_attn_every
        n_cross = cfg.num_layers // seg
        ks, vs = [], []
        for i in range(n_cross):
            sl = slice(i * seg, (i + 1) * seg)
            x, (kc, vc) = layer_scan(
                body, x, (_slice(params["layers"], sl.start, sl.stop),
                          cache["k"][sl], cache["v"][sl]))
            ks.append(kc)
            vs.append(vc)
            cp = jax.tree.map(lambda a: a[i], params["cross"])
            x = _cross_block(cp, cfg, x, None,
                             decode_cache=(cache["ck"][i], cache["cv"][i]))
        rem = cfg.num_layers - n_cross * seg
        if rem:
            x, (kc, vc) = layer_scan(
                body, x, (_slice(params["layers"], n_cross * seg, cfg.num_layers),
                          cache["k"][n_cross * seg:], cache["v"][n_cross * seg:]))
            ks.append(kc)
            vs.append(vc)
        new_k = jnp.concatenate(ks, axis=0)
        new_v = jnp.concatenate(vs, axis=0)
    else:
        n = _dense_prefix(cfg)
        ks, vs = [], []
        if n:
            x, (kc, vc) = layer_scan(
                body, x, (params["dense_layers"], cache[ka][:n],
                          cache[kb][:n]))
            ks.append(kc)
            vs.append(vc)
        x, (kc, vc) = layer_scan(
            body, x, (params["layers"], cache[ka][n:], cache[kb][n:]))
        new_k = jnp.concatenate(ks + [kc], axis=0) if n else kc
        new_v = jnp.concatenate(vs + [vc], axis=0) if n else vc
    x = L.rmsnorm(x, params["final_norm"]["scale"], cfg.norm_eps)
    out = dict(cache, **{ka: new_k, kb: new_v}, pos=pos + 1)
    return L.logits(params["embed"], cfg, x), out
