"""Plain float32 reference of a DeepSeek-V3-style expert stack's training
steps (Moonlight-16B-A3B, ``configs/moonlight-5l.json``).

Follows the published description (DeepSeek-V2/V3, arXiv:2405.04434 and
2412.19437; Moonlight's config.json). Token embedding; per layer RMSNorm
-> multi-head latent attention -> residual -> RMSNorm -> feed-forward ->
residual; a final RMSNorm; the LM head over the vocabulary slice; mean
cross-entropy over the tokens of the rows it is given.

  Latent attention: q = x W_q, H heads of nope + rope (no q compression);
  [c, k_r] = x W_kv_a; c = RMSNorm(c); [k_n, v] = c W_kv_b per head;
  RoPE on q's rope part and on the one k_r every head shares; causal
  softmax of [q_n, q_r].[k_n, k_r] / sqrt(nope + rope); o = P v; o W_o.
  RoPE is the rotate-half form (``reference._rope``). DeepSeek's code
  rotates interleaved pairs; with random weights the two differ only by a
  fixed permutation of W_q's and W_kv_a's rotary columns.
  Leading dense layers: a SwiGLU MLP, down(silu(gate x) * up x).
  Expert layers: scores s = sigmoid(x W_r) over all X experts; a token
  takes the top_k of s + b (b the routing bias, one expert group); its
  weights are the chosen s over their sum times ``routed_scale``. This
  chip's share: experts [held_offset, held_offset + experts_held), each
  computed here on every token and weighted by that token's weight for it
  (0 where the token did not choose it). The shared experts, one SwiGLU of
  ``shared_experts * expert_d_ff``, see every token.
  Sequence-wise balance loss (arXiv:2412.19437 eqs. 17-20), per row and
  layer: f_i = X / (top_k S) * (slots that chose i), P_i = mean over the
  row of s_i / sum_j s_j; alpha * sum_i f_i P_i, summed over the layers
  and averaged over the live rows, added to the loss.
  After each step, per layer: b_i += gamma * sign(mean_j load_j - load_i),
  loads the slots of the step's live rows that chose each expert.

AdamW as the configuration's ``optimizer`` block states it (as
``reference.py``) on every leaf but the routing bias, which has no
gradient, no moments and no decay. It imports nothing of the program. It
runs row by row on the live rows only, every matrix product under
``jax.default_matmul_precision("highest")``, each layer under
``jax.checkpoint`` and attention in blocks of queries, so one 8192-token
row's backward fits beside the reference's own state.

``precision="fp8"`` is the control (both operands of every matrix product
in float8, ``reference._fp8``); ``rows="half"`` trains on the first half
of each step's live rows.
"""
from __future__ import annotations

import functools
import json
import math
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import compare
from chipbench.reference import _mm, _rmsnorm, _rope, _rows, lr_at

QUERY_BLOCK = 512
HEAD_BLOCK = 1024


def _attention(x, p, m, precision):
    """Causal latent self-attention of one row x (S, E)."""
    a, hq = m["mla"], m["num_heads"]
    s = x.shape[0]
    n, r, dv, c = (a["qk_nope_head_dim"], a["qk_rope_head_dim"],
                   a["v_head_dim"], a["kv_lora_rank"])
    q = _mm("se,ef->sf", x, p["wq"], precision).reshape(s, hq, n + r)
    kv = _mm("se,ef->sf", x, p["wkv_a"], precision)
    lat = _rmsnorm(kv[:, :c], p["kv_norm"]["scale"], m["norm_eps"])
    k_r = _rope(kv[:, None, c:], m["rope_theta"])
    kvb = _mm("sc,cf->sf", lat, p["wkv_b"], precision).reshape(s, hq, n + dv)
    q = jnp.concatenate([q[..., :n], _rope(q[..., n:], m["rope_theta"])], -1)
    k = jnp.concatenate([kvb[..., :n], jnp.broadcast_to(k_r, (s, hq, r))], -1)
    v = kvb[..., n:]
    block = QUERY_BLOCK if s % QUERY_BLOCK == 0 else s

    def one(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * block, block, 0)
        scores = _mm("qhd,khd->hqk", qb, k, precision) / math.sqrt(n + r)
        causal = (i * block + jnp.arange(block))[:, None] >= jnp.arange(s)[None, :]
        prob = jax.nn.softmax(jnp.where(causal[None], scores, -jnp.inf), -1)
        return _mm("hqk,khd->qhd", prob, v, precision)

    o = jax.lax.map(jax.checkpoint(one), jnp.arange(s // block))
    return _mm("sf,fe->se", o.reshape(s, hq * dv), p["wo"], precision)


def _swiglu(x, gate, up, down, precision):
    return _mm("sf,fe->se", jax.nn.silu(_mm("se,ef->sf", x, gate, precision))
               * _mm("se,ef->sf", x, up, precision), down, precision)


def _experts(x, p, m, precision):
    """(y, sum_i f_i P_i, loads) of one expert layer on one row."""
    x_cfg = m["moe"]
    n_exp, k = x_cfg["num_experts"], x_cfg["top_k"]
    s = x.shape[0]
    scores = jax.nn.sigmoid(_mm("se,ex->sx", x, p["router"], precision))
    _, top = jax.lax.top_k(scores + jax.lax.stop_gradient(p["route_bias"]), k)
    w = jnp.take_along_axis(scores, top, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * x_cfg["routed_scale"]
    picked = jax.nn.one_hot(top, n_exp)                         # (S, k, X)
    weight = jnp.einsum("skx,sk->sx", picked, w)
    counts = jnp.sum(picked, axis=(0, 1))
    f = counts * n_exp / (k * s)
    share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True), axis=0)
    y = _swiglu(x, p["shared"]["w_gate"], p["shared"]["w_up"],
                p["shared"]["w_down"], precision)
    for i in range(x_cfg["experts_held"]):
        y = y + weight[:, x_cfg["held_offset"] + i, None] * _swiglu(
            x, p["moe_gate"][i], p["moe_up"][i], p["moe_down"][i], precision)
    return y, jnp.sum(f * share), counts


def _layer(x, lp, m, precision):
    """(x, balance term, loads or None) after one layer."""
    h = _rmsnorm(x, lp["norm1"]["scale"], m["norm_eps"])
    x = x + _attention(h, lp["attn"], m, precision)
    h = _rmsnorm(x, lp["norm2"]["scale"], m["norm_eps"])
    if "mlp" in lp:
        mp = lp["mlp"]
        return x + _swiglu(h, mp["w_gate"], mp["w_up"], mp["w_down"],
                           precision), 0.0, None
    y, bal, load = _experts(h, lp["moe"], m, precision)
    return x + y, bal, load


def row_loss(params, tokens, targets, m: Dict, precision: str):
    """(summed cross-entropy of one row, its balance term summed over the
    expert layers, its loads (expert layers, X))."""
    x = params["embed"]["embedding"][tokens].astype(jnp.float32)
    step = jax.checkpoint(functools.partial(_layer, m=m, precision=precision))
    bal, loads = 0.0, []
    for stack in (params.get("dense_layers"), params["layers"]):
        if stack is None:
            continue
        for i in range(jax.tree.leaves(stack)[0].shape[0]):
            x, b, load = step(x, jax.tree.map(lambda a: a[i], stack))
            bal = bal + b
            if load is not None:
                loads.append(load)
    x = _rmsnorm(x, params["final_norm"]["scale"], m["norm_eps"])
    head = params["embed"]["lm_head"][:, : m["vocab_size"]]
    s = x.shape[0]
    block = HEAD_BLOCK if s % HEAD_BLOCK == 0 else s

    def ce(i):
        xb = jax.lax.dynamic_slice_in_dim(x, i * block, block, 0)
        yb = jax.lax.dynamic_slice_in_dim(targets, i * block, block, 0)
        logits = _mm("se,ev->sv", xb, head, precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, yb[:, None], axis=-1)[:, 0]
        return jnp.sum(lse - gold)

    total = jnp.sum(jax.lax.map(jax.checkpoint(ce), jnp.arange(s // block)))
    return total, bal, jnp.stack(loads)


@functools.lru_cache(maxsize=None)
def _row_grad(m_json: str, precision: str):
    """One row's share of the step's objective (its cross-entropy over
    the step's tokens plus alpha times its balance term over the step's
    rows): (its cross-entropy, balance term, loads) and ``sums`` (donated)
    plus its gradient."""
    m = json.loads(m_json)
    alpha = m["moe"]["seq_aux_weight"]

    def objective(p, t, y, n_tok, n_rows):
        ce, bal, loads = row_loss(p, t, y, m, precision)
        return ce / n_tok + alpha * bal / n_rows, (ce, bal, loads)

    grad = jax.value_and_grad(objective, has_aux=True)

    def accumulate(p, sums, t, y, n_tok, n_rows):
        (_, out), g = grad(p, t, y, n_tok, n_rows)
        return out, jax.tree.map(jnp.add, sums, g)

    return jax.jit(accumulate, donate_argnums=(1,))


def _split(params):
    """(the trained tree, the routing bias (expert layers, X))."""
    moe = dict(params["layers"]["moe"])
    bias = moe.pop("route_bias")
    return dict(params, layers=dict(params["layers"], moe=moe)), bias


def _join(trained, bias):
    moe = dict(trained["layers"]["moe"], route_bias=bias)
    return dict(trained, layers=dict(trained["layers"], moe=moe))


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3),
                   static_argnames=("b1", "b2", "eps", "wd", "clip"))
def _adamw(params, mu, nu, grads, t, lr, *, b1, b2, eps, wd, clip):
    """One AdamW step from the step's gradient; returns the clipped
    gradient too. Every tree argument is donated."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    g = jax.tree.map(lambda x: x * jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9)),
                     grads)
    mu = jax.tree.map(lambda a, x: b1 * a + (1 - b1) * x, mu, g)
    nu = jax.tree.map(lambda a, x: b2 * a + (1 - b2) * x * x, nu, g)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, a, b: p - lr * ((a / c1) / (jnp.sqrt(b / c2) + eps) + wd * p),
        params, mu, nu)
    return params, mu, nu, g


def run(m: Dict, opt: Dict, make_params: Callable,
        batches: List[Dict[str, np.ndarray]], precision: str = "f32",
        rows: str = "all") -> Dict[str, Any]:
    """Follow ``batches`` from the weights ``make_params()`` returns:
    {"losses", "grad_norms" (first clipped gradient, per trained leaf),
    "change_norms" (params after the last step minus the first, per
    leaf, the routing bias among them)}."""
    grad_fn = _row_grad(json.dumps(m, sort_keys=True), precision)
    gamma = m["moe"]["bias_update_speed"]
    alpha = m["moe"]["seq_aux_weight"]
    with jax.default_matmul_precision("highest"):
        params, bias = _split(make_params())
        mu = jax.tree.map(jnp.zeros_like, params)
        nu = jax.tree.map(jnp.zeros_like, params)
        losses, grad_norms = [], None
        for t, batch in enumerate(batches, start=1):
            idx = _rows(batch, rows)
            # a step with no rows (half of one) trains on nothing: zero
            # gradients, as the program's step over an all-masked batch
            n_tok = jnp.float32(max(len(idx), 1) * batch["tokens"].shape[1])
            n_rows = jnp.float32(max(len(idx), 1))
            total, bal, load = 0.0, 0.0, jnp.zeros_like(bias)
            full = _join(params, bias)
            sums = jax.tree.map(jnp.zeros_like, full)
            for i in idx:
                (ce, b, ld), sums = grad_fn(
                    full, sums, jnp.asarray(batch["tokens"][i]),
                    jnp.asarray(batch["targets"][i]), n_tok, n_rows)
                total, bal, load = total + float(ce), bal + float(b), load + ld
            sums = _split(sums)[0]
            del full
            losses.append(total / float(n_tok) + alpha * bal / float(n_rows))
            params, mu, nu, clipped = _adamw(
                params, mu, nu, sums, jnp.float32(t),
                jnp.float32(lr_at(opt, t)), b1=opt["b1"], b2=opt["b2"],
                eps=opt["eps"], wd=opt["weight_decay"], clip=opt["clip_norm"])
            bias = bias + gamma * jnp.sign(
                jnp.mean(load, axis=-1, keepdims=True) - load)
            if grad_norms is None:
                grad_norms = compare.leaf_norms(clipped)
            del clipped
        del mu, nu
        return {"losses": losses, "grad_norms": grad_norms,
                "change_norms": compare.change_norms(_join(params, bias),
                                                     make_params())}
