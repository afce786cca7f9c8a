"""Moonlight-16B-A3B's mechanisms at small sizes on the CPU, against plain
jnp written here and against the benchmark's float32 reference
(``chipbench/moe_reference.py``): latent attention, the dropless layer
over a held share of the experts with shared experts, the bias-corrected
sigmoid router, its sign rule and balance loss, and one whole train step.
"""
from __future__ import annotations

import copy
import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced_config
from repro.core import hetero_dp
from repro.kernels import ops
from repro.models import mla as MLA
from repro.models import moe as M
from repro.models.model_factory import build_model

from chipbench import calibrate, cells, moe_reference

SEED = 2**31 + 16


def tiny(**moe):
    cfg = reduced_config(get_arch("moonlight-16b-a3b"))
    return dataclasses.replace(cfg, moe=dataclasses.replace(cfg.moe, **moe))


def model_block(cfg) -> dict:
    """The reference's view of a program config (a file's ``model``)."""
    m, a = cfg.moe, cfg.mla
    return {"num_layers": cfg.num_layers, "num_heads": cfg.num_heads,
            "norm_eps": cfg.norm_eps, "rope_theta": cfg.rope_theta,
            "vocab_size": cfg.vocab_size,
            "mla": dataclasses.asdict(a),
            "moe": {k: getattr(m, k) for k in (
                "num_experts", "top_k", "routed_scale", "seq_aux_weight",
                "bias_update_speed", "held_offset", "dense_prefix")}
            | {"experts_held": m.held}}


def normal(key, shape, scale=1.0):
    return jax.random.normal(key, shape, jnp.float32) * scale


# ---------------------------------------------------------------------------
# latent attention
# ---------------------------------------------------------------------------


def naive_mla(p, cfg, x):
    """The block's equations in einsums, rotate-half RoPE, full softmax."""
    a, h = cfg.mla, cfg.num_heads
    s = x.shape[1]
    n, r, c = a.qk_nope_head_dim, a.qk_rope_head_dim, a.kv_lora_rank
    q = jnp.einsum("bse,ef->bsf", x, p["wq"]).reshape(*x.shape[:2], h, n + r)
    kv = jnp.einsum("bse,ef->bsf", x, p["wkv_a"])
    lat = kv[..., :c] * jax.lax.rsqrt(
        jnp.mean(kv[..., :c] ** 2, -1, keepdims=True) + cfg.norm_eps)
    lat = lat * p["kv_norm"]["scale"]
    kvb = jnp.einsum("bsc,cf->bsf", lat, p["wkv_b"]).reshape(
        *x.shape[:2], h, n + a.v_head_dim)
    inv = 1.0 / cfg.rope_theta ** (jnp.arange(0, r, 2) / r)
    ang = jnp.arange(s)[:, None] * inv[None]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[:, None]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[:, None]

    def rope(t):
        return t * cos + jnp.concatenate([-t[..., r // 2:], t[..., :r // 2]],
                                         -1) * sin

    qf = jnp.concatenate([q[..., :n], rope(q[..., n:])], -1)
    kr = rope(kv[..., None, c:])
    kf = jnp.concatenate([kvb[..., :n], jnp.broadcast_to(
        kr, kvb.shape[:3] + (r,))], -1)
    scores = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) / math.sqrt(n + r)
    causal = jnp.tril(jnp.ones((s, s), bool))
    prob = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), -1)
    o = jnp.einsum("bhqk,bkhd->bqhd", prob, kvb[..., n:])
    return o.reshape(*x.shape[:2], -1) @ p["wo"]


def test_mla_block_matches_naive_einsums():
    cfg = tiny()
    p = MLA.init_mla(jax.random.PRNGKey(0), cfg)
    p["kv_norm"]["scale"] = 1.0 + normal(jax.random.PRNGKey(2), (32,), 0.1)
    x = normal(jax.random.PRNGKey(1), (2, 24, cfg.d_model))
    with jax.default_matmul_precision("highest"):
        got = MLA.mla_block(p, cfg, x, jnp.arange(24))
        want = naive_mla(p, cfg, x)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


def test_splash_serves_latent_heads_as_blocked_does():
    """q/k heads of 192 beside v heads of 128: the splash kernel (interpret
    mode) and the blocked scan agree, forward and gradients, at the
    1/sqrt(192) scale."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = normal(ks[0], (1, 256, 2, 192))
    k = normal(ks[1], (1, 256, 2, 192))
    v = normal(ks[2], (1, 256, 2, 128))

    def run(impl):
        f = lambda q, k, v: ops.attention(q, k, v, impl=impl)
        out, vjp = jax.vjp(f, q, k, v)
        return (out,) + vjp(jnp.cos(out))

    for name, a, b in zip(("out", "dq", "dk", "dv"), run("splash"),
                          run("blocked")):
        assert a.shape == b.shape, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=2e-4, err_msg=name)


def test_tpu_dispatch_takes_latent_heads_to_the_fused_kernel(monkeypatch):
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    seen = []
    q = jax.ShapeDtypeStruct((1, 256, 4, 192), jnp.bfloat16)
    v = jax.ShapeDtypeStruct((1, 256, 4, 128), jnp.bfloat16)
    with ops.observe_attention(lambda path, why: seen.append((path, why))):
        out = jax.eval_shape(lambda q, k, v: ops.attention(q, k, v), q, q, v)
    assert seen == [("fused", "")] and out.shape == (1, 256, 4, 128)


# ---------------------------------------------------------------------------
# grouped products and the dropless held-experts layer
# ---------------------------------------------------------------------------


def test_grouped_matmul_megablox_matches_ragged_dot():
    """The fused path (megablox in interpret mode) computes only the rows
    its groups hold, zeroes the rest, and has ragged_dot's gradients."""
    ks = jax.random.split(jax.random.PRNGKey(4), 3)
    x = normal(ks[0], (256, 128))
    w = normal(ks[1], (3, 128, 256))
    sizes = jnp.array([50, 0, 70], jnp.int32)

    def run(impl):
        f = lambda x, w: ops.grouped_matmul(x, w, sizes, impl=impl)
        out, vjp = jax.vjp(f, x, w)
        return (out,) + vjp(normal(ks[2], out.shape))

    got, want = run("pallas"), run("blocked")
    assert float(jnp.abs(got[0][120:]).max()) == 0.0
    np.testing.assert_allclose(np.asarray(got[0][:50]),
                               np.asarray(x[:50] @ w[0]), rtol=1e-4, atol=1e-3)
    for a, b in zip(got, want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=2e-3)


def held_layer(key, cfg):
    p = M.init_held_experts(key, cfg)
    p["route_bias"] = normal(jax.random.fold_in(key, 9), p["route_bias"].shape,
                             0.05)
    return p


def test_held_shares_add_up_to_the_uncut_layer():
    """The partial outputs of all shares (offsets 0, 4) with the shared
    experts counted once equal the layer that holds every expert."""
    whole = tiny(experts_held=0)
    x = normal(jax.random.PRNGKey(5), (2, 16, whole.d_model))
    p = held_layer(jax.random.PRNGKey(6), whole)
    with jax.default_matmul_precision("highest"):
        want, _, _ = M.held_experts_block(p, whole, x)
        sp = p["shared"]
        shared = (jax.nn.silu(x @ sp["w_gate"]) * (x @ sp["w_up"])) @ sp["w_down"]
        total = -shared          # two shares, each with the shared experts
        held = 0
        for off in range(0, 8, 4):
            cut = tiny(experts_held=4, held_offset=off)
            part = dict(p, **{k: p[k][off:off + 4]
                              for k in ("moe_gate", "moe_up", "moe_down")})
            y, _, st = M.held_experts_block(part, cut, x)
            total = total + y
            held += int(st["rows"].sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert held == 2 * 16 * 2      # every token-slot computed once


def full_size_held_experts(p, m, x, top_i, w):
    """The held experts at full size, the reference the bounded buffer
    is held to: every token-slot broadcast to (T k, E), permuted by held
    expert, the products over all T k rows, permuted back and summed over
    each token's slots."""
    t, e = x.shape
    k, H = top_i.shape[1], m.held
    local = top_i.reshape(-1) - m.held_offset
    held = (local >= 0) & (local < H)
    group = jnp.where(held, local, H)
    order = jnp.argsort(group, stable=True)
    inverse = jnp.argsort(order)
    rows = jnp.sum(jax.nn.one_hot(group, H + 1, dtype=jnp.int32), axis=0)[:H]
    xs = jnp.broadcast_to(x[:, None, :], (t, k, e)).reshape(t * k, e)[order]
    dt = x.dtype
    gate = ops.grouped_matmul(xs, p["moe_gate"].astype(dt), rows)
    up = ops.grouped_matmul(xs, p["moe_up"].astype(dt), rows)
    out = ops.grouped_matmul(jax.nn.silu(gate) * up,
                             p["moe_down"].astype(dt), rows)
    ws = jnp.where(held, w.reshape(-1), 0.0)[order]
    part = (out.astype(jnp.float32) * ws[:, None]).astype(dt)
    y = part[inverse].reshape(t, k, e)
    return (jnp.sum(y.astype(jnp.float32), axis=1), rows, jnp.sum(held),
            jnp.zeros((), jnp.int32))


# 512 tokens of 2 slots over 2 held experts of 8: a buffer of 512 rows,
# below the 1024 held slots 512 tokens can have
BOUNDED = dict(experts_held=2, held_offset=2)
TOKENS = 512


@pytest.mark.parametrize("bias,overflow", [
    (None, 0),                       # ~256 held slots: the buffer's path
    ({2: 10.0, 3: 5.0}, 1),          # all 1024 held: every window in turn
])
def test_bounded_buffer_matches_the_full_size_path(monkeypatch, bias,
                                                   overflow):
    """The held experts over their bounded buffer (on the CPU the
    products take ``ragged_dot``, the blocked path) against the
    full-size path, output and the gradients of x, the three expert
    weights and the router, in f32 at the highest precision."""
    cfg = tiny(**BOUNDED)
    m = cfg.moe
    assert M._buffer_rows(TOKENS, m.top_k, m.held, m.num_experts) == 512
    p = held_layer(jax.random.PRNGKey(13), cfg)
    if bias:
        p["route_bias"] = jnp.zeros((8,)).at[jnp.array(list(bias))].set(
            jnp.array(list(bias.values())))
    x = normal(jax.random.PRNGKey(14), (2, TOKENS // 2, cfg.d_model))
    cot = normal(jax.random.PRNGKey(15), x.shape)
    names = ("router", "moe_gate", "moe_up", "moe_down")

    def run():
        def loss(x, q):
            y, aux, st = M.held_experts_block(dict(p, **q), cfg, x)
            return jnp.sum(y * cot) + aux, st
        with jax.default_matmul_precision("highest"):
            (_, st), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(
                x, {n: p[n] for n in names})
        return st, grads

    st, (gx, gp) = run()
    assert int(st["overflow"]) == overflow
    assert int(st["dropped"]) == 0
    assert (int(st["rows"].sum()) > 512) == bool(overflow)
    with jax.default_matmul_precision("highest"):
        y, _, _ = M.held_experts_block(p, cfg, x)
    monkeypatch.setattr(M, "_held_experts", full_size_held_experts)
    want_st, (want_gx, want_gp) = run()
    with jax.default_matmul_precision("highest"):
        want, _, _ = M.held_experts_block(p, cfg, x)
    np.testing.assert_array_equal(np.asarray(st["rows"]),
                                  np.asarray(want_st["rows"]))
    # f32 sums taken in another order: within 1e-6 of each array's largest
    for name, a, b in [("y", y, want), ("x", gx, want_gx)] + [
            (n, gp[n], want_gp[n]) for n in names]:
        b = np.asarray(b)
        np.testing.assert_allclose(np.asarray(a), b, rtol=1e-5,
                                   atol=1e-6 * float(np.abs(b).max()),
                                   err_msg=name)


def check_imbalanced_layer(share, tokens, bias, rows, overflow):
    """A bias that sends every token to held experts: nothing is
    dropped, and the output is the reference's."""
    cfg = tiny(**share)
    p = held_layer(jax.random.PRNGKey(7), cfg)
    p["route_bias"] = jnp.zeros((8,)).at[jnp.array(list(bias))].set(
        jnp.array(list(bias.values())))
    x = normal(jax.random.PRNGKey(8), (1, tokens, cfg.d_model))
    m = model_block(cfg)
    with jax.default_matmul_precision("highest"):
        y, aux, st = M.held_experts_block(p, cfg, x)
        want, bal, load = moe_reference._experts(x[0], p, m, "f32")
    np.testing.assert_allclose(np.asarray(y[0]), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    assert int(st["dropped"]) == 0
    assert int(st["overflow"]) == overflow
    assert st["rows"].tolist() == rows
    np.testing.assert_allclose(float(aux), m["moe"]["seq_aux_weight"]
                               * float(bal), rtol=1e-5)
    np.testing.assert_array_equal(np.asarray(st["load"]), np.asarray(load))


def test_held_layer_matches_reference_and_drops_nothing_under_imbalance():
    """Every token to held expert 2 and one held elsewhere: 32 held slots
    at most, so the buffer holds them all."""
    check_imbalanced_layer(dict(experts_held=4, held_offset=0), 32,
                           {2: 10.0, 6: 5.0}, [0, 0, 32, 0], 0)


def test_held_layer_overflowing_its_buffer_matches_reference():
    """Every slot of 512 tokens to the 2 held experts: 1024 held slots
    over a buffer of 512 run every window of it, and still nothing is
    dropped."""
    check_imbalanced_layer(BOUNDED, TOKENS, {2: 10.0, 3: 5.0},
                           [TOKENS, TOKENS], 1)


def test_bias_sign_rule_and_balance_loss_skip_masked_rows():
    cfg = tiny()
    p = held_layer(jax.random.PRNGKey(10), cfg)
    x = normal(jax.random.PRNGKey(11), (3, 16, cfg.d_model))
    live = jnp.array([1.0, 0.0, 1.0])
    top_i, _, aux, load = M.route(p, cfg.moe, x, live)
    counts = jax.nn.one_hot(top_i, 8).sum(axis=(1, 2))
    np.testing.assert_array_equal(np.asarray(load),
                                  np.asarray(counts[0] + counts[2]))
    # the masked row changes neither the loads nor the loss
    x2 = x.at[1].set(normal(jax.random.PRNGKey(12), (16, cfg.d_model), 5.0))
    _, _, aux2, load2 = M.route(p, cfg.moe, x2, live)
    np.testing.assert_array_equal(np.asarray(load2), np.asarray(load))
    assert float(aux2) == pytest.approx(float(aux), rel=1e-6)
    # the mean of the live rows' sum_i f_i P_i, times alpha
    m = model_block(cfg)
    per_row = [moe_reference._experts(x[i], p, m, "f32")[1] for i in (0, 2)]
    assert float(aux) == pytest.approx(
        cfg.moe.seq_aux_weight * float(np.mean(per_row)), rel=1e-5)
    bias = M.update_route_bias(p["route_bias"], load, 1e-3)
    want = p["route_bias"] + 1e-3 * np.sign(np.mean(load) - np.asarray(load))
    np.testing.assert_allclose(np.asarray(bias), want, rtol=0, atol=1e-9)


def test_step_keeps_the_bias_out_of_adamw_and_counts_rows():
    cfg = tiny(experts_held=4)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    from repro.optim.optimizer import AdamW, OptConfig
    opt = AdamW(OptConfig(weight_decay=0.5, warmup_steps=0))
    state = opt.init(params)
    assert "route_bias" not in state.mu["layers"]["moe"]
    step = jax.jit(hetero_dp.make_train_step(model, opt))
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, 256)
    batch = {"tokens": toks[:, :-1], "targets": toks[:, 1:],
             "sample_mask": jnp.array([1.0, 0.0])}
    new, _, out = step(params, state, batch)
    moved = np.asarray(new["layers"]["moe"]["route_bias"]
                       - params["layers"]["moe"]["route_bias"])
    # the sign rule's step or none: no AdamW update, no weight decay
    step_size = np.abs(moved)
    assert np.all(np.isclose(step_size, 1e-3, rtol=1e-4)
                  | (step_size == 0.0)), moved
    assert int(out["moe.rows.dropped"]) == 0
    assert int(out["moe.rows.overflow"]) == 0
    # every token-slot of both rows (masked ones are computed too) lands
    # on a held expert or elsewhere
    assert 0 < int(out["moe.rows.held"]) <= 2 * 16 * 2
    assert int(out["moe.rows.max_expert"]) <= int(out["moe.rows.held"])


def test_trainer_counts_every_routing_counter():
    """The trainer's counters take each of the step's ``moe.rows.*``
    counts, ``moe.rows.overflow`` among them."""
    from repro.core.allocator import solve
    from repro.core.speed_model import SpeedModel
    from repro.launch.train import HeteroTrainer, TrainerConfig
    from repro.obs import MetricsRegistry

    sm = SpeedModel(np.array([1.0, 2, 4, 8]), np.array([10.0, 18, 28, 30]))
    t = HeteroTrainer(tiny(experts_held=4), solve({"g": (1, sm)}, 4096),
                      TrainerConfig(seq_len=16, steps=1, log_every=0,
                                    dataset_size=4096))
    t.metrics = MetricsRegistry()
    t.run(1)
    names = [n for n in t.metrics.names() if n.startswith("moe.rows.")]
    assert sorted(names) == sorted(hetero_dp.ROUTING_COUNTERS)
    assert "moe.rows.overflow" in names
    assert t.metrics.get("moe.rows.overflow").value == 0
    assert t.metrics.get("moe.rows.dropped").value == 0
    assert t.metrics.get("moe.rows.held").value > 0


# ---------------------------------------------------------------------------
# one whole step against the benchmark's reference
# ---------------------------------------------------------------------------

WIDTHS = {"d_model": 64, "num_heads": 4, "num_kv_heads": 4, "d_ff": 128,
          "vocab_size": 256}
SMALL_MLA = {"kv_lora_rank": 32, "qk_nope_head_dim": 16,
             "qk_rope_head_dim": 8, "v_head_dim": 16}


def small_cell():
    """The benchmark's moonlight-5l cell with every width cut, the layers,
    expert counts, held share and traffic kept, computing in f32."""
    bench = json.loads(cells.BENCHMARK.read_text())
    cell = copy.deepcopy(cells.load_cell("moonlight-5l.s8192.steady", bench))
    m, over = cell.config["model"], cell.config["overrides"]
    m.update(WIDTHS, compute_dtype="float32")
    m["mla"] = dict(SMALL_MLA)
    m["moe"]["expert_d_ff"] = 32
    over.update(WIDTHS, dtype="float32", mla=dict(SMALL_MLA))
    over["moe"] = dict(over["moe"], expert_d_ff=32)
    cell.traffic["seq_len"] = 32
    return cell


def test_train_step_matches_the_reference():
    """Three steps of the trainer's compiled step (HeteroTrainer, the
    capacity-masked step) against the f32 reference: only summation order
    separates the two."""
    cell = small_cell()
    with jax.default_matmul_precision("highest"):
        r = calibrate.readings_for_seed(cell, SEED, faults=False)
    assert r["rows_distinct"]
    assert r["program"]["loss_gap"][0] < 1e-5
    assert r["program"]["grad_gap"][0] < 1e-4
    assert r["program"]["update_gap"][0] < 1e-4


def test_work_module_counts_the_cells_parameters_and_flops():
    """``moe_flops`` against the program's arrays for the moonlight-5l
    file (the vocabulary slice is a multiple of 256, so unpadded), and
    its per-token forward FLOPs against a count by hand."""
    from chipbench import harness, moe_flops

    config = cells.load_cell("moonlight-5l.s8192.steady").config
    m = config["model"]
    arch = harness.make_arch(config)
    shapes = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    assert sum(x.size for x in jax.tree.leaves(shapes)) == \
        moe_flops.param_count(m) == 568_484_608
    mla = 2048 * 16 * 192 + 2048 * 576 + 512 * 16 * 256 + 16 * 128 * 2048
    core = 16 * (192 + 128) * 8193
    expert = 3 * 2048 * 1408
    per_layer_experts = 2 * (2048 * 64 + 2 * expert + expert * 6 * 8 / 64)
    want = (5 * (2 * mla + core) + 2 * 3 * 2048 * 11264
            + 4 * per_layer_experts + 2 * 2048 * 20480)
    assert moe_flops.forward_flops_per_token(m, 8192) == pytest.approx(want)
    assert moe_flops.forward_flops_per_token(m, 8192) == pytest.approx(
        761.0e6, rel=1e-4)
