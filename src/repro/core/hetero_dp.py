"""Capacity-masked heterogeneous data parallelism (DESIGN.md §2/§4).

XLA SPMD needs static uniform shapes, so per-group batch sizes b_g live
inside a fixed-capacity global batch as a row-validity mask:

  loss = Σ_tokens (ce * sample_mask) / Σ_tokens sample_mask

which makes the masked-capacity gradient EXACTLY the ragged-batch gradient
(property-tested). Retuning b_g between steps changes mask contents only —
no recompilation, no epoch restart (beyond-paper improvement §9).
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import BatchPlan, row_mask
from repro.models import layers as L
from repro.models import moe as M
from repro.models import shardings as sh
from repro.models.model_factory import Model

NEG_INF = -1e30

# the jitted train step's name: its HLO module is ``jit_<STEP_NAME>``
STEP_NAME = "hypertune_train_step"

# per-step routing counts a step of a dropless expert stack returns:
# token-slots its held experts computed (summed over layers), the largest
# held expert's rows in one layer, token-slots routed to a held expert
# that no product computed (0 by construction), and the layers whose held
# slots overflowed their buffer and ran over every window of it
ROUTING_COUNTERS = ("moe.rows.held", "moe.rows.max_expert", "moe.rows.dropped",
                    "moe.rows.overflow")


def _routes(model: Model) -> bool:
    """Whether the model's expert layers keep a routing bias (their loads
    leave the forward pass)."""
    return model.cfg.moe is not None and model.cfg.moe.router == "noaux_tc"


def cross_entropy(logits: jnp.ndarray, targets: jnp.ndarray,
                  vocab_size: int) -> jnp.ndarray:
    """Per-token CE in f32; vocab padding columns masked to -inf."""
    lg = logits.astype(jnp.float32)
    vp = lg.shape[-1]
    if vp != vocab_size:
        col = jnp.arange(vp)
        lg = jnp.where(col[None, None, :] < vocab_size, lg, NEG_INF)
    lse = jax.nn.logsumexp(lg, axis=-1)
    lab = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return lse - lab


def token_mask(batch: Dict[str, Any], seq_len: int) -> jnp.ndarray:
    """(B, S) f32 mask = sample mask × optional per-token mask."""
    m = batch["sample_mask"][:, None].astype(jnp.float32)
    m = jnp.broadcast_to(m, (batch["tokens"].shape[0], seq_len))
    if "token_mask" in batch:
        m = m * batch["token_mask"].astype(jnp.float32)
    return m


def chunked_ce_sums(model: Model, params, hidden: jnp.ndarray,
                    targets: jnp.ndarray, tok_mask: jnp.ndarray,
                    chunk: int) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Streamed loss head (§Perf lever): CE over sequence chunks so the
    (B, S, V) logits tensor is never materialized — per chunk the live
    working set is (B, chunk, V). jax.checkpoint on the chunk body keeps
    the backward pass at the same footprint."""
    cfg = model.cfg
    b, s, _ = hidden.shape
    chunk = min(chunk, s)
    while s % chunk:
        chunk -= 1                      # largest divisor <= requested
    n = s // chunk

    def body(carry, i):
        tot, cnt = carry
        h = jax.lax.dynamic_slice_in_dim(hidden, i * chunk, chunk, 1)
        t = jax.lax.dynamic_slice_in_dim(targets, i * chunk, chunk, 1)
        m = jax.lax.dynamic_slice_in_dim(tok_mask, i * chunk, chunk, 1)
        with jax.named_scope("loss_head"):
            lg = L.logits(params["embed"], cfg, h)
            ce = cross_entropy(lg, t, cfg.vocab_size)
        return (tot + (ce * m).sum(), cnt + m.sum()), None

    from repro.models.scan_util import layer_scan
    body = jax.checkpoint(body)
    (tot, cnt), _ = layer_scan(
        body, (jnp.zeros((), jnp.float32), jnp.zeros((), jnp.float32)),
        jnp.arange(n))
    return tot, cnt


def loss_sums(model: Model, params, batch: Dict[str, Any],
              remat=True, ce_chunk: int = 0) -> Tuple:
    """(Σ ce·mask, Σ mask, aux, routing stats) — the unnormalized pieces,
    so microbatch accumulation can normalize by the GLOBAL token count
    exactly. The stats are {} unless the expert layers keep a routing
    bias."""
    seq = batch["tokens"].shape[1]
    m = token_mask(batch, seq)
    if _routes(model):
        hidden, aux, routing = model.forward(
            params, batch, remat=remat, return_hidden=True, with_stats=True)
    else:
        hidden, aux = model.forward(params, batch, remat=remat,
                                    return_hidden=True)
        routing = {}
    if ce_chunk:
        tot, cnt = chunked_ce_sums(model, params, hidden, batch["targets"],
                                   m, ce_chunk)
        return tot, cnt, aux, routing
    with jax.named_scope("loss_head"):
        logits = L.logits(params["embed"], model.cfg, hidden)
        ce = cross_entropy(logits, batch["targets"], model.cfg.vocab_size)
    return (ce * m).sum(), m.sum(), aux, routing


def masked_loss(model: Model, params, batch: Dict[str, Any],
                remat=True, ce_chunk: int = 0
                ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    tot, cnt, aux, routing = loss_sums(model, params, batch, remat=remat,
                                       ce_chunk=ce_chunk)
    loss = tot / jnp.maximum(cnt, 1.0)
    metrics = {"ce": loss, "aux": aux, "tokens": cnt}
    if routing:
        metrics["routing"] = routing
    return loss + aux, metrics


def make_train_step(model: Model, optimizer, remat=True,
                    ce_chunk: int = 0, micro_batches: int = 1,
                    grad_dtype=None) -> Callable:
    """Build the pjit-able synchronous train step.

    micro_batches > 1 scans gradient accumulation over batch slices
    (activation HBM / m; grads accumulate in f32). The accumulated
    gradient is EXACTLY the single-shot gradient: each microbatch
    contributes grad(Σce)/T_global with T_global known from the masks
    up front, plus grad(aux)/m.
    """

    def single_step(params, opt_state, batch):
        def lf(p):
            return masked_loss(model, p, batch, remat=remat,
                               ce_chunk=ce_chunk)
        (loss, metrics), grads = jax.value_and_grad(lf, has_aux=True)(params)
        routing = metrics.pop("routing", None)
        if grad_dtype is not None:
            # narrow the cross-replica gradient all-reduce (§Perf lever);
            # the optimizer re-widens to f32 internally
            grads = jax.tree.map(lambda g: g.astype(grad_dtype), grads)
        params, opt_state = _apply_update(optimizer, grads, opt_state,
                                          params)
        if routing:
            params = _route_bias_step(model, params, routing)
            metrics.update(_routing_counts(routing))
        gn = optimizer.last_grad_norm(opt_state)
        return params, opt_state, {"loss": loss, "grad_norm": gn, **metrics}

    if micro_batches <= 1:
        return _named(single_step)

    def accum_step(params, opt_state, batch):
        m = micro_batches
        B = batch["tokens"].shape[0]
        assert B % m == 0, (B, m)
        seq = batch["tokens"].shape[1]
        t_global = jnp.maximum(token_mask(batch, seq).sum(), 1.0)

        bspec = sh.batch_spec()

        def resh(x):
            if hasattr(x, "shape") and x.ndim >= 1 and x.shape[0] == B:
                y = x.reshape(m, B // m, *x.shape[1:])
                return sh.constrain(y, None, bspec,
                                    *([None] * (y.ndim - 2)))
            return x

        mb = {k: resh(v) for k, v in batch.items()}

        def body(gacc, mb_i):
            def lf(p):
                tot, cnt, aux, routing = loss_sums(
                    model, p, mb_i, remat=remat, ce_chunk=ce_chunk)
                return tot / t_global + aux / m, (tot, cnt, aux, routing)
            (_, out), g = jax.value_and_grad(lf, has_aux=True)(params)
            gacc = jax.tree.map(
                lambda a, b: a + b.astype(jnp.float32), gacc, g)
            return gacc, out

        from repro.models.scan_util import layer_scan
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        grads, (tots, cnts, auxs, routing) = layer_scan(body, g0, mb)
        grads = jax.tree.map(lambda g, p: g.astype(p.dtype), grads, params)
        ce = tots.sum() / t_global
        aux = auxs.mean()
        params, opt_state = _apply_update(optimizer, grads, opt_state,
                                          params)
        metrics = {"ce": ce, "aux": aux, "tokens": cnts.sum()}
        if routing:
            # loads and rows add over the microbatches
            routing = jax.tree.map(lambda a: a.sum(axis=0), routing)
            params = _route_bias_step(model, params, routing)
            metrics.update(_routing_counts(routing))
        gn = optimizer.last_grad_norm(opt_state)
        return params, opt_state, {"loss": ce + aux, "grad_norm": gn,
                                   **metrics}

    return _named(accum_step)


def _apply_update(optimizer, grads, opt_state, params):
    """The optimizer's update and its addition to the parameters, under
    the ``optimizer`` name scope."""
    with jax.named_scope("optimizer"):
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = jax.tree.map(lambda p, u: p + u, params, updates)
    return params, opt_state


def _route_bias_step(model: Model, params, routing):
    """Each expert layer's routing bias after the step: DeepSeek-V3's sign
    rule on the loads of the step's live tokens (``routing["load"]``,
    layers x experts), under the ``moe`` / ``router`` name scopes."""
    with jax.named_scope("moe"), jax.named_scope("router"):
        mp = params["layers"]["moe"]
        bias = M.update_route_bias(mp["route_bias"], routing["load"],
                                   model.cfg.moe.bias_update_speed)
    return {**params, "layers": {**params["layers"],
                                 "moe": {**mp, "route_bias": bias}}}


def _routing_counts(routing) -> Dict[str, jnp.ndarray]:
    """The step's ``ROUTING_COUNTERS`` from its routing stats."""
    rows = routing["rows"]
    return dict(zip(ROUTING_COUNTERS,
                    (jnp.sum(rows), jnp.max(rows),
                     jnp.sum(routing["dropped"]),
                     jnp.sum(routing["overflow"]))))


def _named(step: Callable) -> Callable:
    step.__name__ = step.__qualname__ = STEP_NAME
    return step


def make_eval_step(model: Model, remat: bool = False) -> Callable:
    def eval_step(params, batch):
        loss, metrics = masked_loss(model, params, batch, remat=remat)
        return {"loss": loss, **metrics}
    return eval_step


def make_prefill_step(model: Model) -> Callable:
    def prefill_step(params, batch):
        logits, _ = model.forward(params, batch, remat=False)
        return logits
    return prefill_step


def make_serve_step(model: Model) -> Callable:
    def serve_step(params, cache, tokens, aux=None):
        return model.decode_step(params, cache, tokens, aux)
    return serve_step


# ---------------------------------------------------------------------------
# batch layout <-> plan
# ---------------------------------------------------------------------------


class HeteroBatchLayout:
    """Maps BatchPlan groups onto contiguous row blocks of the global batch.

    Row blocks are sized by CAPACITY (static); live rows per block follow
    the plan's current batch sizes (dynamic, data-only).
    """

    def __init__(self, plan: BatchPlan):
        self.capacities = [(g.name, g.capacity * g.count) for g in plan.groups]
        self.total_rows = sum(c for _, c in self.capacities)

    def mask(self, plan: BatchPlan) -> np.ndarray:
        m = row_mask(plan)
        assert len(m) == self.total_rows, (len(m), self.total_rows)
        return m

    def group_rows(self, name: str) -> Tuple[int, int]:
        start = 0
        for n, c in self.capacities:
            if n == name:
                return start, start + c
            start += c
        raise KeyError(name)


def pad_global_batch(batch_rows: int, multiple: int) -> int:
    """Round the capacity batch up so the mesh batch axes divide it."""
    return ((batch_rows + multiple - 1) // multiple) * multiple
