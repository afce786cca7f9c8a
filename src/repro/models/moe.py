"""Top-k MoE layer with capacity-based scatter dispatch.

Dispatch is grouped **per batch row** for S>1 (each row dispatches its own S
tokens — fully local under batch sharding, zero cross-shard traffic), and as
a single global group for decode (S==1), where the scatter/gather across the
data axis is the all-to-all analogue.

Expert weights are tensor-sharded on their FF dim by default (works for any
expert count); expert-parallel placement (experts on the model axis) is a
config/hillclimb option handled in shardings.py.
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.kernels import ops
from repro.models import shardings as sh

Params = dict


def init_moe(key, cfg: ArchConfig, out_scale: float = 1.0) -> Params:
    m = cfg.moe
    E, F, X = cfg.d_model, m.expert_d_ff, m.num_experts
    ks = jax.random.split(key, 4)
    s_in = 1.0 / (E ** 0.5)
    s_out = out_scale / (F ** 0.5)
    return {
        "router": jax.random.normal(ks[0], (E, X), jnp.float32) * s_in,
        "moe_gate": jax.random.normal(ks[1], (X, E, F), jnp.float32) * s_in,
        "moe_up": jax.random.normal(ks[2], (X, E, F), jnp.float32) * s_in,
        "moe_down": jax.random.normal(ks[3], (X, F, E), jnp.float32) * s_out,
    }


def _capacity(tokens_per_group: int, cfg: ArchConfig) -> int:
    m = cfg.moe
    c = int(-(-tokens_per_group * m.top_k * m.capacity_factor // m.num_experts))
    return max(c, 1)


def moe_block(p: Params, cfg: ArchConfig, x: jnp.ndarray
              ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """x (B, S, E) -> (y (B, S, E), aux_loss scalar)."""
    m = cfg.moe
    X, k = m.num_experts, m.top_k
    b, s, e = x.shape
    dt = x.dtype
    if s > 1:
        x = sh.constrain(x, sh.batch_spec(), None, None)  # gather seq shards
    if s > 1:
        g, t = b, s                    # per-row groups (local dispatch)
    else:
        g, t = 1, b                    # decode: one global group
    xg = x.reshape(g, t, e)
    cap = _capacity(t, cfg)

    # --- routing (f32) ---
    logits = (xg.astype(jnp.float32) @ p["router"])             # (G,T,X)
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)                      # (G,T,k)
    gates = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # aux load-balance loss (switch-style), computed over all groups
    me = probs.mean(axis=(0, 1))                                # (X,)
    assign = jax.nn.one_hot(top_i[..., 0], X, dtype=jnp.float32).mean(axis=(0, 1))
    aux = X * jnp.sum(me * assign) * m.aux_loss_weight

    # --- position-in-expert via per-slot cumsum ---
    gidx = jnp.arange(g)[:, None]
    counts = jnp.zeros((g, X), jnp.int32)
    disp = jnp.zeros((g, X, cap, e), dt)
    combined = jnp.zeros((g, t, e), jnp.float32)
    slot_data = []
    for slot in range(k):
        ei = top_i[..., slot]                                   # (G,T)
        onehot = jax.nn.one_hot(ei, X, dtype=jnp.int32)         # (G,T,X)
        pos_all = jnp.cumsum(onehot, axis=1) - 1 + counts[:, None, :]
        pos = jnp.take_along_axis(pos_all, ei[..., None], -1)[..., 0]
        counts = counts + onehot.sum(axis=1)
        keep = (pos < cap)
        pos_c = jnp.minimum(pos, cap - 1)
        disp = disp.at[gidx, ei, pos_c].add(
            xg * keep[..., None].astype(dt), mode="drop")
        slot_data.append((ei, pos_c, keep))

    disp = sh.constrain(disp, sh.batch_spec() if g > 1 else None,
                        None, None, None)

    # --- expert FFN (SwiGLU) ---
    w_g = p["moe_gate"].astype(dt)
    w_u = p["moe_up"].astype(dt)
    w_d = p["moe_down"].astype(dt)
    h = jax.nn.silu(jnp.einsum("gxce,xef->gxcf", disp, w_g))
    h = h * jnp.einsum("gxce,xef->gxcf", disp, w_u)
    h = sh.constrain(h, sh.batch_spec() if g > 1 else None, None, None, "model")
    out = jnp.einsum("gxcf,xfe->gxce", h, w_d)                  # (G,X,C,E)
    out = sh.constrain(out, sh.batch_spec() if g > 1 else None, None, None, None)
    # (combine-before-psum via implicit constraints was tried and made the
    # schedule WORSE — XLA inserted collective-permutes; the explicit
    # shard_map version lives in moe_ep.moe_block_fs. EXPERIMENTS.md §Perf.)

    # --- combine ---
    out32 = out.astype(jnp.float32)
    for slot, (ei, pos_c, keep) in enumerate(slot_data):
        gathered = out32[gidx[..., None], ei[..., None],
                         pos_c[..., None]][..., 0, :]           # (G,T,E)
        w = gates[..., slot] * keep.astype(jnp.float32)
        combined = combined + gathered * w[..., None]

    y = combined.reshape(b, s, e).astype(dt)
    from repro.models.layers import named
    return named(sh.constrain_act(y, "res"), "ffn_out"), aux


# ---------------------------------------------------------------------------
# DeepSeek-V3 expert layer: dropless over the experts this device holds
# ---------------------------------------------------------------------------
#
# The router scores every expert (sigmoid, f32); each token takes the top_k
# of score + routing bias, weighted by its chosen scores (without the bias)
# over their sum, times ``routed_scale``. The layer holds experts
# [held_offset, held_offset + held) and computes their part of
# sum_k w_k SwiGLU_k(x) for every token-slot routed to them: the slots are
# sorted by held expert into a buffer sized to twice the uniform held share
# and run through grouped products whose work follows the rows routed; a
# step that holds more runs the buffer over every window of sorted slots,
# so nothing is dropped whatever the imbalance. The shared
# experts (one SwiGLU of shared_experts x expert_d_ff) see every token. On
# one device the layer runs without the exchange expert parallelism adds.


def init_held_experts(key, cfg: ArchConfig, out_scale: float = 1.0) -> Params:
    m = cfg.moe
    E, F, X, H = cfg.d_model, m.expert_d_ff, m.num_experts, m.held
    ks = jax.random.split(key, 5)
    s_in = 1.0 / (E ** 0.5)
    s_out = out_scale / (F ** 0.5)
    p = {
        "router": jax.random.normal(ks[0], (E, X), jnp.float32) * s_in,
        "route_bias": jnp.zeros((X,), jnp.float32),
        "moe_gate": jax.random.normal(ks[1], (H, E, F), jnp.float32) * s_in,
        "moe_up": jax.random.normal(ks[2], (H, E, F), jnp.float32) * s_in,
        "moe_down": jax.random.normal(ks[3], (H, F, E), jnp.float32) * s_out,
    }
    if m.shared_experts:
        from repro.models.layers import init_mlp
        p["shared"] = init_mlp(ks[4], cfg, d_ff=m.shared_experts * F,
                               out_scale=out_scale)
    return p


def route(p: Params, m, x: jnp.ndarray, live: jnp.ndarray):
    """x (B, S, E); live (B,) row mask. Returns the chosen experts (B, S, k),
    their weights (B, S, k, f32), the sequence-wise balance loss (alpha
    times the mean over live rows of sum_i f_i P_i, DeepSeek-V3 eqs.
    17-20, over all experts) and each expert's load: token-slots of live
    rows that chose it (X,)."""
    X, k = m.num_experts, m.top_k
    s = x.shape[1]
    scores = jax.nn.sigmoid(x.astype(jnp.float32) @ p["router"])
    bias = jax.lax.stop_gradient(p["route_bias"])
    _, top_i = jax.lax.top_k(scores + bias, k)
    w = jnp.take_along_axis(scores, top_i, axis=-1)
    w = w / jnp.sum(w, axis=-1, keepdims=True) * m.routed_scale
    chosen = jnp.sum(jax.nn.one_hot(top_i, X, dtype=jnp.float32),
                     axis=(1, 2))                                 # (B, X)
    f = chosen * (X / (k * s))
    share = jnp.mean(scores / jnp.sum(scores, axis=-1, keepdims=True),
                     axis=1)                                      # (B, X)
    per_row = jnp.sum(f * share, axis=-1)
    aux = m.seq_aux_weight * jnp.sum(per_row * live) / jnp.maximum(
        jnp.sum(live), 1.0)
    load = jnp.sum(chosen * live[:, None], axis=0)
    return top_i, w, aux, load


def _buffer_rows(t: int, k: int, held: int, experts: int) -> int:
    """Rows of the held token-slots' buffer for ``t`` tokens of ``k``
    slots: twice the uniform held share, ``2 t k held / experts``, rounded
    up to the grouped product's row tile, and at most ``t min(k, held)``,
    the most held slots ``t`` tokens can have."""
    tile = ops.GMM_ROW_TILE
    share = -(-2 * t * k * held // (experts * tile)) * tile
    return min(share, t * min(k, held))


def _token_sums(b, tok, t: int):
    """(t, E) f32: row i sums the rows of ``b`` (R, E) whose token
    ``tok`` is i, a scatter-add over the R rows (its gradient is the
    gather ``g[tok]``)."""
    return jnp.zeros((t, b.shape[1]), jnp.float32).at[tok].add(
        b.astype(jnp.float32))


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _token_rows(x, tok, t: int):
    """``x[tok]`` for ``x`` of ``t`` rows: each buffer row's token, rows
    of ``x`` repeating; its gradient sums each token's rows in f32."""
    return x[tok]


def _token_rows_fwd(x, tok, t):
    return x[tok], tok


def _token_rows_bwd(t, tok, g):
    return _token_sums(g, tok, t).astype(g.dtype), None


_token_rows.defvjp(_token_rows_fwd, _token_rows_bwd)


def _window_rows(rows, lo: int, r: int):
    """Of ``rows`` (H,) held slots per expert, laid out by expert from
    sorted position 0, how many of each lie in positions [lo, lo + r)."""
    end = jnp.cumsum(rows)
    return jnp.clip(end, lo, lo + r) - jnp.clip(end - rows, lo, lo + r)


def _experts_over(x, mats, rows, ws, kept):
    """The held experts over a buffer of token-slots ``kept`` (R,), held
    ones first by expert (``rows`` of them per expert; the rest come out
    zero): x (T, E) gathered by token, the three grouped products over R
    rows, each slot's part weighted by ``ws`` (T k,) in f32 and summed
    into its token in f32. Returns (T, E) f32."""
    t = x.shape[0]
    tok = kept // (ws.shape[0] // t)
    xs = _token_rows(x, tok, t)
    gate_w, up_w, down_w = mats
    gate = ops.grouped_matmul(xs, gate_w, rows)
    up = ops.grouped_matmul(xs, up_w, rows)
    out = ops.grouped_matmul(jax.nn.silu(gate) * up, down_w, rows)
    part = (out.astype(jnp.float32) * ws[kept][:, None]).astype(x.dtype)
    return _token_sums(part, tok, t)


def _held_experts(p: Params, m, x: jnp.ndarray, top_i, w):
    """x (T, E); top_i, w (T, k). This device's experts' part of the
    routed sum, (T, E) f32, the rows each held expert computed (H,), the
    token-slots routed to them, and whether those overflowed the buffer
    of ``_buffer_rows`` (0 or 1).

    The slots are sorted once, held ones first by expert, and the first
    R run through ``_experts_over``. Where more than R are held, every
    window of R sorted slots runs in turn, each rematerialised, so that
    nothing is dropped and no residual outgrows a window."""
    t = x.shape[0]
    k, H = top_i.shape[1], m.held
    local = top_i.reshape(-1) - m.held_offset
    held = (local >= 0) & (local < H)
    group = jnp.where(held, local, H)                  # H: held elsewhere
    order = jnp.argsort(group, stable=True)
    rows = jnp.sum(jax.nn.one_hot(group, H + 1, dtype=jnp.int32), axis=0)[:H]
    routed = jnp.sum(held)
    ws = jnp.where(held, w.reshape(-1), 0.0)
    mats = tuple(p[n].astype(x.dtype) for n in ("moe_gate", "moe_up",
                                                "moe_down"))
    r = _buffer_rows(t, k, H, m.num_experts)
    n = -(-t * min(k, H) // r)                     # windows of every held slot

    def first_window(x, mats, rows, ws, order):
        return _experts_over(x, mats, rows, ws, order[:r])

    if n == 1:
        return (first_window(x, mats, rows, ws, order), rows, routed,
                jnp.zeros((), jnp.int32))

    def every_window(x, mats, rows, ws, order):
        order = jnp.pad(order, (0, max(0, n * r - t * k)))[:n * r]
        window = jax.checkpoint(_experts_over)

        def add(y, i):
            kept = jax.lax.dynamic_slice_in_dim(order, i * r, r)
            return y + window(x, mats, _window_rows(rows, i * r, r), ws,
                              kept), None

        return jax.lax.scan(add, jnp.zeros(x.shape, jnp.float32),
                            jnp.arange(n))[0]

    overflow = routed > r
    y = jax.lax.cond(overflow, every_window, first_window,
                     x, mats, rows, ws, order)
    return y, rows, routed, overflow.astype(jnp.int32)


def held_experts_block(p: Params, cfg: ArchConfig, x: jnp.ndarray,
                       live=None) -> Tuple[jnp.ndarray, jnp.ndarray, dict]:
    """x (B, S, E), ``live`` (B,) the row mask (None: all rows) ->
    (y, balance loss, stats): ``load`` (X,) the routing bias's input,
    ``rows`` (H,) token-slots each held expert computed, ``dropped``
    token-slots routed to a held expert that no product computed (0),
    ``overflow`` 1 where the held slots overflowed the buffer and ran
    over every window of it, else 0."""
    m = cfg.moe
    b, s, e = x.shape
    live = jnp.ones((b,), jnp.float32) if live is None else live
    with jax.named_scope("moe"):
        with jax.named_scope("router"):
            top_i, w, aux, load = route(p, m, x, live.astype(jnp.float32))
        with jax.named_scope("experts"):
            y, rows, routed, overflow = _held_experts(
                p, m, x.reshape(b * s, e), top_i.reshape(b * s, -1),
                w.reshape(b * s, -1))
            y = y.reshape(b, s, e)
        if "shared" in p:
            with jax.named_scope("shared_experts"):
                sp = p["shared"]
                dt = x.dtype
                h = jax.nn.silu(x @ sp["w_gate"].astype(dt)) * (
                    x @ sp["w_up"].astype(dt))
                y = y + (h @ sp["w_down"].astype(dt)).astype(jnp.float32)
        from repro.models.layers import named
        stats = {"load": jax.lax.stop_gradient(load), "rows": rows,
                 "dropped": routed - jnp.sum(rows), "overflow": overflow}
        return named(y.astype(x.dtype), "ffn_out"), aux, stats


def update_route_bias(bias: jnp.ndarray, load: jnp.ndarray,
                      speed: float) -> jnp.ndarray:
    """DeepSeek-V3's sign rule, per layer: b_i += speed * sign(mean load -
    load_i); ``bias`` and ``load`` (..., X)."""
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + speed * jnp.sign(mean - load)
