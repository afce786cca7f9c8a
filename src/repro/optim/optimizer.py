"""AdamW with global-norm clipping, warmup+cosine schedule, and optional
gradient compression (error-feedback) — self-contained pytree impl.
"""
from __future__ import annotations

import dataclasses
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp

from repro.optim import compression as C


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    warmup_steps: int = 100
    total_steps: int = 10_000
    min_lr_frac: float = 0.1
    schedule: str = "cosine"            # cosine | linear | const
    compression: str = "none"           # none | bf16 | int8


# parameter-tree keys whose leaves are state kept by a rule of their own
# (the expert router's bias, models/moe.py), not trained: they get no
# moments, no decay, no clipping share and a zero update here
STATE_KEYS = frozenset({"route_bias"})


def trained(tree):
    """The parameter tree without its state leaves."""
    if isinstance(tree, dict):
        return {k: trained(v) for k, v in tree.items() if k not in STATE_KEYS}
    return tree


def _zeros_for_state(full, part):
    """``part`` (a trained tree) completed to ``full``'s structure with
    zeros at the state leaves."""
    if isinstance(full, dict):
        return {k: (jax.tree.map(jnp.zeros_like, v) if k in STATE_KEYS
                    else _zeros_for_state(v, part[k]))
                for k, v in full.items()}
    return part


class OptState(NamedTuple):
    step: jnp.ndarray
    mu: Any
    nu: Any
    grad_norm: jnp.ndarray
    ef: Any                              # error-feedback residual (or None)


def schedule(cfg: OptConfig, step: jnp.ndarray) -> jnp.ndarray:
    s = step.astype(jnp.float32)
    warm = jnp.minimum(s / jnp.maximum(cfg.warmup_steps, 1), 1.0)
    frac = jnp.clip((s - cfg.warmup_steps)
                    / jnp.maximum(cfg.total_steps - cfg.warmup_steps, 1), 0, 1)
    if cfg.schedule == "cosine":
        decay = cfg.min_lr_frac + (1 - cfg.min_lr_frac) * 0.5 * (
            1 + jnp.cos(jnp.pi * frac))
    elif cfg.schedule == "linear":
        decay = 1.0 - (1.0 - cfg.min_lr_frac) * frac
    else:
        decay = jnp.asarray(1.0)
    return cfg.lr * warm * decay


class AdamW:
    def __init__(self, cfg: OptConfig):
        self.cfg = cfg

    def init(self, params) -> OptState:
        params = trained(params)
        zeros = lambda: jax.tree.map(jnp.zeros_like, params)
        ef = zeros() if self.cfg.compression != "none" else None
        return OptState(jnp.zeros((), jnp.int32), zeros(), zeros(),
                        jnp.zeros(()), ef)

    def update(self, grads, state: OptState, params):
        full = params
        grads, params = trained(grads), trained(params)
        cfg = self.cfg
        # gradient compression with error feedback (DCN-bound gradients)
        ef = state.ef
        if cfg.compression != "none":
            grads, ef = C.compress_with_feedback(grads, ef, cfg.compression)
        # global-norm clip
        gn = jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                          for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gn, 1e-9))
        grads = jax.tree.map(lambda g: g * scale, grads)
        step = state.step + 1
        lr = schedule(cfg, step)
        b1, b2 = cfg.b1, cfg.b2
        mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, state.mu, grads)
        nu = jax.tree.map(lambda n, g: b2 * n + (1 - b2) * jnp.square(g),
                          state.nu, grads)
        c1 = 1 - b1 ** step.astype(jnp.float32)
        c2 = 1 - b2 ** step.astype(jnp.float32)

        def upd(m, n, p):
            mhat = m / c1
            nhat = n / c2
            u = -lr * (mhat / (jnp.sqrt(nhat) + cfg.eps)
                       + cfg.weight_decay * p)
            return u.astype(p.dtype)

        updates = jax.tree.map(upd, mu, nu, params)
        return _zeros_for_state(full, updates), OptState(step, mu, nu, gn, ef)

    @staticmethod
    def last_grad_norm(state: OptState) -> jnp.ndarray:
        return state.grad_norm
