"""Compiles for a described TPU v5e, with no chip attached.

The TPU compiler refuses what interpret mode accepts (block shapes off
the (8, 128) tiling, ops Mosaic cannot lower, programs larger than the
chip's HBM). These cases compile the Pallas kernels at real widths with
``interpret=False``, and the whole train steps of chip_smoke.py and of
the yi9b-2l benchmark cell, for one chip of a ``v5e:2x2`` topology.
Nothing runs: results are checked by tests/test_kernels.py, times only
on the chip.

The topology is described inside a fixture, never at import: one process
at a time may load the TPU library, and every test worker imports this
file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import os
import pathlib
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.base import get_arch
from repro.core import hetero_dp
from repro.kernels import flash_attention as fa
from repro.kernels import ops
from repro.kernels import ssd_scan as ssd
from repro.models.model_factory import build_model
from repro.optim.optimizer import AdamW, OptConfig

REPO = pathlib.Path(__file__).resolve().parents[1]
V5E_HBM = 16e9


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def topo(no_persistent_cache):
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                             topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def test_flash_attention_compiles(one_chip):
    q = _spec(one_chip, (1, 32, 2048, 128))           # head_dim 128
    compiled = jax.jit(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=False)).lower(q, q, q).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_ssd_scan_compiles(one_chip):
    # mamba2-1.3b: d_inner 4096 = 64 heads x head_dim 64, state 128
    b, h, s, p, n = 1, 64, 2048, 64, 128
    f32 = jnp.float32
    args = (_spec(one_chip, (b, h, s, p)), _spec(one_chip, (b, h, s), f32),
            _spec(one_chip, (h,), f32), _spec(one_chip, (b, s, n)),
            _spec(one_chip, (b, s, n)), _spec(one_chip, (h,), f32))
    compiled = jax.jit(lambda *a: ssd.ssd_scan(
        *a, chunk=256, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_chip_smoke_step_fits_one_chip(one_chip):
    """chip_smoke.py's train step at its largest capacity (its probe
    ladder's top rung for each of three nodes) fits one v5e with at
    least 2 GB to spare."""
    cs = _chip_smoke()
    arch = dataclasses.replace(get_arch("deepseek-7b"),
                               num_layers=cs.LAYERS, vocab_size=cs.VOCAB)
    model, opt = build_model(arch), AdamW(OptConfig())

    def init():
        params = model.init(jax.random.PRNGKey(0))
        return params, opt.init(params)

    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(init))
    rows = cs.PROBE_LADDER[-1] * 3
    batch = {"tokens": _spec(one_chip, (rows, cs.SEQ_LEN), jnp.int32),
             "targets": _spec(one_chip, (rows, cs.SEQ_LEN), jnp.int32),
             "sample_mask": _spec(one_chip, (rows,), jnp.float32)}
    compiled = jax.jit(hetero_dp.make_train_step(model, opt)).lower(
        *state, batch).compile()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= V5E_HBM - 2e9, f"{total / 1e9:.2f} GB"


def test_yi9b_step_attention_lowers_to_the_splash_kernels(one_chip,
                                                         monkeypatch):
    """The yi9b-2l benchmark cell's train step (6 x 1024, 32 q / 4 kv
    heads of 128, remat) with the dispatch seeing a TPU backend: the
    attention scope lowers to the splash kernels' custom calls and no f32
    (B, H, Sq, 512) score block of the blocked scan is left."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    arch = dataclasses.replace(get_arch("yi-9b"), num_layers=2,
                               vocab_size=8000)
    model, opt = build_model(arch), AdamW(OptConfig())

    def init():
        params = model.init(jax.random.PRNGKey(0))
        return params, opt.init(params)

    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(init))
    batch = {"tokens": _spec(one_chip, (6, 1024), jnp.int32),
             "targets": _spec(one_chip, (6, 1024), jnp.int32),
             "sample_mask": _spec(one_chip, (6,), jnp.float32)}
    seen = []
    with ops.observe_attention(lambda path, why: seen.append(path)):
        lowered = jax.jit(hetero_dp.make_train_step(model, opt)).lower(
            *state, batch)
    assert seen and set(seen) == {"fused"}
    hlo = lowered.compile().as_text()
    # a custom call's text spans lines (its kernel metadata): match across
    calls = re.findall(r"%(splash_mha_\w+?)\.\d+ = [^\n]*custom-call\("
                       r".*?op_name=\"([^\"]*)\"", hlo, re.S)
    kinds = {name.split("_")[2] for name, _ in calls}
    assert kinds == {"fwd", "dq", "dkv"}, kinds
    assert all("/attention/" in op for _, op in calls)
    assert not re.search(r"f32\[6,32,1024,512\]", hlo)


def test_moonlight_step_lowers_to_fused_kernels_and_fits(one_chip,
                                                         monkeypatch):
    """The moonlight-5l cell's train step (one 8192-token row; latent
    attention at q/k 192, v 128; 8 held experts of 64) with the dispatch
    seeing a TPU: every attention call takes the splash kernels, the held
    experts' products megablox's ``gmm`` and ``tgmm``, and the step fits
    one v5e's 15.75 GiB the compiler allows. Its bytes stay within the
    15.196 GB the step took with its held experts over all T k
    token-slots (15.02 GB over the buffer of 12,288)."""
    monkeypatch.setattr(ops, "_backend", lambda: "tpu")
    arch = dataclasses.replace(
        get_arch("moonlight-16b-a3b"), num_layers=5, vocab_size=20480,
        moe=dataclasses.replace(get_arch("moonlight-16b-a3b").moe,
                                experts_held=8))
    model, opt = build_model(arch), AdamW(OptConfig())

    def init():
        params = model.init(jax.random.PRNGKey(0))
        return params, opt.init(params)

    state = jax.tree.map(lambda a: _spec(one_chip, a.shape, a.dtype),
                         jax.eval_shape(init))
    batch = {"tokens": _spec(one_chip, (1, 8192), jnp.int32),
             "targets": _spec(one_chip, (1, 8192), jnp.int32),
             "sample_mask": _spec(one_chip, (1,), jnp.float32)}
    seen = []
    with ops.observe_attention(lambda path, why: seen.append(path)):
        lowered = jax.jit(hetero_dp.make_train_step(model, opt)).lower(
            *state, batch)
    assert seen == ["fused", "fused"]
    compiled = lowered.compile()
    hlo = compiled.as_text()
    for kernel in ("splash_mha_fwd", "gmm", "tgmm"):
        assert re.search(rf"%{kernel}\w*\.\d+ = [^\n]*custom-call\(", hlo), \
            kernel
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total <= 15.75 * 2**30, f"{total / 1e9:.2f} GB"
    assert total <= 15.196e9, f"{total / 1e9:.3f} GB"
