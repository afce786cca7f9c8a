"""ShapeDtypeStruct input specs + sharding specs for every (arch × shape).

No device allocation happens here — everything is abstract (the shannon/
kernels pattern): ``jax.eval_shape`` for params/opt/cache, ShapeDtypeStruct
for batches.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchConfig, ShapeConfig
from repro.models import shardings as sh
from repro.models.model_factory import Model, aux_inputs


def batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract train/prefill batch."""
    gb, s = shape.global_batch, shape.seq_len
    out = {
        "tokens": jax.ShapeDtypeStruct((gb, s), jnp.int32),
        "targets": jax.ShapeDtypeStruct((gb, s), jnp.int32),
        "sample_mask": jax.ShapeDtypeStruct((gb,), jnp.float32),
    }
    out.update(aux_inputs(cfg, gb, s, jnp.bfloat16, concrete=False))
    return out


def decode_specs(model: Model, shape: ShapeConfig
                 ) -> Tuple[Any, Any, Optional[Dict]]:
    """(cache_shapes, token_spec, aux_specs) for one serve step."""
    cfg = model.cfg
    gb, s = shape.global_batch, shape.seq_len
    aux = aux_inputs(cfg, gb, s, jnp.bfloat16, concrete=False) or None
    params_shape = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    if aux is None:
        cache_shape = jax.eval_shape(
            lambda p: model.init_cache(p, gb, s, jnp.bfloat16, None),
            params_shape)
    else:
        cache_shape = jax.eval_shape(
            lambda p, a: model.init_cache(p, gb, s, jnp.bfloat16, a),
            params_shape, aux)
    tok = jax.ShapeDtypeStruct((gb, 1), jnp.int32)
    return cache_shape, tok, aux


# ---------------------------------------------------------------------------
# sharding specs
# ---------------------------------------------------------------------------


def _bspec(mesh: Mesh):
    ax = sh.batch_axes(mesh)
    return ax if len(ax) > 1 else (ax[0] if ax else None)


def batch_shardings(batch_tree, mesh: Mesh):
    b = _bspec(mesh)

    def rule(path, leaf):
        nd = len(leaf.shape)
        spec = P(b, *([None] * (nd - 1))) if nd else P()
        return NamedSharding(mesh, sh.adapt_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(rule, batch_tree)


def cache_shardings(cache_tree, cfg: ArchConfig, mesh: Mesh):
    """KV/SSM cache placement (DESIGN.md §6).

    Heads go on the model axis when divisible; otherwise the SEQUENCE dim
    is model-sharded (sharded-softmax decode) so huge caches still fit.
    """
    b = _bspec(mesh)
    tp = mesh.shape["model"]
    kv_ok = cfg.num_kv_heads > 0 and cfg.num_kv_heads % tp == 0

    def rule(path, leaf):
        name = path[-1].key if hasattr(path[-1], "key") else str(path[-1])
        nd = len(leaf.shape)
        if name in ("k", "v", "ck", "cv") and nd == 5:
            spec = P(None, b, None, "model", None) if kv_ok \
                else P(None, b, "model", None, None)
        elif name == "ssm":
            spec = P(None, b, "model", None, None) if nd == 5 \
                else P(b, "model", None, None)
        elif name == "conv":
            spec = P(None, b, None, None) if nd == 4 else P(b, None, None)
        elif name == "pos":
            spec = P(b)
        else:
            spec = P(*([None] * nd))
        return NamedSharding(mesh, sh.adapt_spec(spec, leaf.shape, mesh))

    return jax.tree_util.tree_map_with_path(rule, cache_tree)


def param_shardings(params_tree, cfg: ArchConfig, mesh: Mesh,
                    moe_expert_parallel: bool = False):
    specs = sh.param_specs(params_tree, cfg, mesh,
                           moe_expert_parallel=moe_expert_parallel)
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def opt_shardings(opt_state_shape, param_shardings_tree, mesh: Mesh,
                  zero1: bool = False):
    """mu/nu/ef mirror the param placement; scalars replicated.

    zero1=True additionally shards the f32 moments over the DATA axis
    (ZeRO-1): the first spec-free dim the data axis divides — usually the
    stacked-layer dim — so each data rank owns 1/|data| of the optimizer
    state. XLA inserts the corresponding update-gather; measured in
    EXPERIMENTS.md §Perf (the HBM lever for the 47B-param mixtral).
    """
    from repro.optim.optimizer import OptState, trained
    rep = NamedSharding(mesh, P())
    param_shardings_tree = trained(param_shardings_tree)

    def z1(ns, leaf):
        spec = list(tuple(ns.spec)) + [None] * (len(leaf.shape)
                                                - len(tuple(ns.spec)))
        for i, (dim, ax) in enumerate(zip(leaf.shape, spec)):
            if ax is None and dim % mesh.shape["data"] == 0 and dim > 1:
                spec[i] = "data"
                break
        return NamedSharding(mesh, P(*spec))

    if zero1:
        moments = jax.tree.map(z1, param_shardings_tree,
                               jax.tree.map(lambda x: x, opt_state_shape.mu))
    else:
        moments = param_shardings_tree
    return OptState(
        step=rep,
        mu=moments,
        nu=moments,
        grad_norm=rep,
        ef=None if opt_state_shape.ef is None else moments,
    )
