"""Per-architecture smoke tests (reduced configs, deliverable f) and
prefill↔decode consistency."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, list_archs, reduced_config
from repro.core import hetero_dp
from repro.models.model_factory import aux_inputs, build_model
from repro.optim.optimizer import AdamW, OptConfig

from conftest import ALL_ARCHS, make_batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
class TestSmoke:
    def test_train_step_shapes_and_finite(self, arch, tiny_models):
        cfg, model = tiny_models(arch)
        params = model.init(jax.random.PRNGKey(0))
        opt = AdamW(OptConfig())
        opt_state = opt.init(params)
        step = jax.jit(hetero_dp.make_train_step(model, opt, remat=True))
        batch = make_batch(cfg, 4, 32)
        params, opt_state, m = step(params, opt_state, batch)
        assert np.isfinite(float(m["loss"]))
        assert np.isfinite(float(m["grad_norm"]))
        for leaf in jax.tree.leaves(params):
            assert bool(jnp.all(jnp.isfinite(leaf))), "non-finite param"

    def test_forward_logit_shape(self, arch, tiny_models):
        cfg, model = tiny_models(arch)
        params = model.init(jax.random.PRNGKey(0))
        batch = make_batch(cfg, 2, 16)
        logits, aux = model.forward(params, batch, remat=False)
        assert logits.shape[:2] == (2, 16)
        assert logits.shape[2] >= cfg.vocab_size
        assert np.isfinite(np.asarray(logits)).all()

    def test_decode_step_advances_cache(self, arch, tiny_models):
        cfg, model = tiny_models(arch)
        params = model.init(jax.random.PRNGKey(0))
        aux = aux_inputs(cfg, 2, 16, jnp.float32, concrete=True) or None
        cache = model.init_cache(params, 2, 16, jnp.float32, aux)
        tok = jnp.ones((2, 1), jnp.int32)
        logits, cache2 = model.decode_step(params, cache, tok, aux)
        assert logits.shape[:2] == (2, 1)
        assert np.isfinite(np.asarray(logits)).all()
        if "pos" in cache2:
            assert int(cache2["pos"][0]) == int(cache["pos"][0]) + 1


def _no_drop(cfg):
    """MoE capacity-factor high enough that no token is ever dropped —
    otherwise teacher-forced prefill (per-row dispatch groups) and
    token-by-token decode (global group) legitimately diverge on dropped
    tokens."""
    import dataclasses
    if cfg.moe is None:
        return cfg
    return dataclasses.replace(
        cfg, moe=dataclasses.replace(cfg.moe, capacity_factor=8.0))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_prefill_decode_consistency(arch):
    """Token-by-token decode reproduces the teacher-forced forward logits.

    This pins the cache layout, RoPE offsets, ring buffers, SSM state
    updates and cross-attention caches all at once.
    """
    cfg = _no_drop(reduced_config(get_arch(arch)))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 2, 12
    batch = make_batch(cfg, B, S, key=jax.random.PRNGKey(7))
    aux = {k: v for k, v in batch.items()
           if k in ("img_embeds", "enc_frames")} or None

    full_logits, _ = model.forward(params, batch, remat=False)

    cache = model.init_cache(params, B, S + 1, jnp.float32, aux)
    step = jax.jit(model.decode_step)
    got = []
    for t in range(S):
        logits, cache = step(params, cache, batch["tokens"][:, t:t + 1], aux)
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    v = min(got.shape[-1], full_logits.shape[-1])
    np.testing.assert_allclose(np.asarray(got[..., :v]),
                               np.asarray(full_logits[..., :v]),
                               rtol=5e-3, atol=5e-3)


def test_sliding_window_ring_buffer_matches_full_history():
    """Mixtral-style SWA: decode with a W-slot ring buffer == decode with
    the full cache + window mask."""
    cfg = _no_drop(reduced_config(get_arch("mixtral-8x7b"),
                                  sliding_window=8, num_layers=2))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    B, S = 1, 20
    batch = make_batch(cfg, B, S, key=jax.random.PRNGKey(3))
    full_logits, _ = model.forward(params, batch, remat=False)

    cache = model.init_cache(params, B, S + 1, jnp.float32, None)
    assert cache["k"].shape[2] == 8            # ring buffer, not full length
    step = jax.jit(model.decode_step)
    got = []
    for t in range(S):
        logits, cache = step(params, cache, batch["tokens"][:, t:t + 1], None)
        got.append(logits[:, 0])
    got = jnp.stack(got, axis=1)
    v = min(got.shape[-1], full_logits.shape[-1])
    np.testing.assert_allclose(np.asarray(got[..., :v]),
                               np.asarray(full_logits[..., :v]),
                               rtol=5e-3, atol=5e-3)


def test_moe_routes_to_multiple_experts():
    cfg = reduced_config(get_arch("mixtral-8x7b"))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = make_batch(cfg, 2, 16)
    logits, aux = model.forward(params, batch, remat=False)
    assert float(aux) > 0.0                     # load-balance loss active


def test_moe_aux_loss_scales_with_imbalance():
    from repro.models import moe as M
    cfg = reduced_config(get_arch("mixtral-8x7b"))
    p = M.init_moe(jax.random.PRNGKey(0), cfg)
    # balanced: random inputs, random router -> aux ~ weight
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    _, aux_bal = M.moe_block(p, cfg, x)
    # imbalanced: constant inputs + router pushing everything to expert 0
    # -> aux -> X * weight (switch-style load-balance penalty)
    router = jnp.zeros_like(p["router"]).at[:, 0].set(1.0)
    _, aux_imb = M.moe_block(dict(p, router=router), cfg,
                             jnp.ones_like(x))
    assert float(aux_imb) > 2.0 * float(aux_bal)


def test_param_count_analytic_matches_actual():
    """ArchConfig.param_count (used for MODEL_FLOPS) vs real init sizes."""
    for arch in ("deepseek-7b", "mixtral-8x7b", "mamba2-1.3b", "zamba2-1.2b",
                 "moonlight-16b-a3b"):
        cfg = reduced_config(get_arch(arch))
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        actual = sum(int(np.prod(l.shape)) for l in jax.tree.leaves(params))
        # padded vocab + minor bias terms allowed: 15%
        assert abs(actual - cfg.param_count()) / actual < 0.15, arch


def test_full_configs_match_assignment():
    """The registered FULL configs carry the assigned hyper-parameters."""
    spec = {
        "zamba2-1.2b": dict(num_layers=38, d_model=2048, num_heads=32,
                            num_kv_heads=32, d_ff=8192, vocab_size=32000),
        "codeqwen1.5-7b": dict(num_layers=32, d_model=4096, num_heads=32,
                               num_kv_heads=32, d_ff=13440, vocab_size=92416),
        "yi-9b": dict(num_layers=48, d_model=4096, num_heads=32,
                      num_kv_heads=4, d_ff=11008, vocab_size=64000),
        "qwen1.5-4b": dict(num_layers=40, d_model=2560, num_heads=20,
                           num_kv_heads=20, d_ff=6912, vocab_size=151936,
                           qkv_bias=True),
        "deepseek-7b": dict(num_layers=30, d_model=4096, num_heads=32,
                            num_kv_heads=32, d_ff=11008, vocab_size=102400),
        "llama-3.2-vision-11b": dict(num_layers=40, d_model=4096,
                                     num_heads=32, num_kv_heads=8,
                                     d_ff=14336, vocab_size=128256),
        "mamba2-1.3b": dict(num_layers=48, d_model=2048, num_heads=0,
                            vocab_size=50280),
        "whisper-tiny": dict(num_layers=4, d_model=384, num_heads=6,
                             num_kv_heads=6, d_ff=1536, vocab_size=51865),
        "mixtral-8x7b": dict(num_layers=32, d_model=4096, num_heads=32,
                             num_kv_heads=8, vocab_size=32000),
        "moonshot-v1-16b-a3b": dict(num_layers=48, d_model=2048,
                                    num_heads=16, num_kv_heads=16,
                                    vocab_size=163840),
        "moonlight-16b-a3b": dict(num_layers=27, d_model=2048, num_heads=16,
                                  num_kv_heads=16, d_ff=11264,
                                  vocab_size=163840, rope_theta=50000.0),
    }
    for arch, want in spec.items():
        cfg = get_arch(arch)
        for k, v in want.items():
            assert getattr(cfg, k) == v, f"{arch}.{k}"
    assert get_arch("mamba2-1.3b").ssm.state_dim == 128
    assert get_arch("zamba2-1.2b").ssm.state_dim == 64
    m = get_arch("mixtral-8x7b").moe
    assert (m.num_experts, m.top_k, m.expert_d_ff) == (8, 2, 14336)
    m = get_arch("moonshot-v1-16b-a3b").moe
    assert (m.num_experts, m.top_k, m.expert_d_ff) == (64, 6, 1408)
    ml = get_arch("moonlight-16b-a3b")
    m = ml.moe
    assert (m.num_experts, m.top_k, m.expert_d_ff, m.shared_experts,
            m.dense_prefix, m.router, m.routed_scale) == (
        64, 6, 1408, 2, 1, "noaux_tc", 2.446)
    assert (ml.mla.kv_lora_rank, ml.mla.qk_nope_head_dim,
            ml.mla.qk_rope_head_dim, ml.mla.v_head_dim) == (512, 128, 64, 128)
    # 15.96B parameters in all, 2.9B active a token: the catalog's 16B-A3B
    assert round(ml.param_count() / 1e9, 2) == 15.96
    assert round(ml.active_param_count() / 1e9, 1) == 2.9


def test_long_500k_applicability():
    """long_500k runs only for sub-quadratic families (DESIGN.md §5)."""
    runs = {a for a in list_archs()
            if "long_500k" in get_arch(a).applicable_shapes()}
    assert runs == {"zamba2-1.2b", "mamba2-1.3b", "mixtral-8x7b"}
