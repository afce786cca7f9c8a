"""Family dispatch: one uniform Model API over all 10 architectures.

Model:
  init(key)                      -> params
  forward(params, batch, remat)  -> (logits, aux_loss)   # train / prefill
                                    (+ routing stats, with_stats=True:
                                     transformer family only)
  init_cache(params, B, max_len, dtype, aux) -> cache
  decode_step(params, cache, tokens, aux)    -> (logits, cache)
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict

import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import encdec, hybrid, mamba2, transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ArchConfig
    init: Callable
    forward: Callable
    init_cache: Callable
    decode_step: Callable


def build_model(cfg: ArchConfig) -> Model:
    fam = cfg.family
    if fam in ("dense", "moe", "vlm"):
        mod = transformer
        init_cache = mod.init_cache
    elif fam == "ssm":
        mod = mamba2
        init_cache = lambda params, cfg_, b, mlen, dt, aux=None: \
            mamba2.init_cache(cfg_, b, mlen, dt)
    elif fam == "hybrid":
        mod = hybrid
        init_cache = mod.init_cache
    elif fam == "audio":
        mod = encdec
        init_cache = mod.init_cache
    else:
        raise ValueError(f"unknown family {fam}")

    if fam == "ssm":
        decode = lambda params, cfg_, cache, tok, aux=None: \
            mamba2.decode_step(params, cfg_, cache, tok, aux)
    else:
        decode = mod.decode_step

    return Model(
        cfg=cfg,
        init=lambda key: mod.init(key, cfg),
        forward=lambda params, batch, remat=True, return_hidden=False, **kw:
            mod.forward(params, cfg, batch, remat=remat,
                        return_hidden=return_hidden, **kw),
        init_cache=lambda params, b, mlen, dtype, aux=None: init_cache(
            params, cfg, b, mlen, dtype, aux),
        decode_step=lambda params, cache, tok, aux=None: decode(
            params, cfg, cache, tok, aux),
    )


def aux_inputs(cfg: ArchConfig, batch_size: int, seq_len: int,
               dtype=jnp.bfloat16, concrete: bool = False) -> Dict[str, Any]:
    """Modality-frontend STUB inputs (shapes; concrete zeros if asked)."""
    import jax
    out: Dict[str, Any] = {}
    if cfg.cross_attn_every:
        shape = (batch_size, cfg.num_image_tokens, cfg.d_model)
        out["img_embeds"] = (jnp.zeros(shape, dtype) if concrete
                             else jax.ShapeDtypeStruct(shape, dtype))
    if cfg.is_encoder_decoder:
        enc_len = min(seq_len, cfg.max_encoder_len)
        shape = (batch_size, enc_len, cfg.d_model)
        out["enc_frames"] = (jnp.zeros(shape, dtype) if concrete
                             else jax.ShapeDtypeStruct(shape, dtype))
    return out
