"""Sharding rules (spec level, via AbstractMesh) and a real reduced-scale
multi-device lower+compile in a subprocess (8 host devices)."""
from __future__ import annotations

import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.configs.base import get_arch
from repro.models import shardings as sh


MESH = AbstractMesh((16, 16), ("data", "model"))
POD_MESH = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


class TestAdaptSpec:
    def test_divisible_dims_keep_axes(self):
        assert sh.adapt_spec(P("model", None), (32, 7), MESH) == \
            P("model", None)

    def test_non_divisible_dims_replicate(self):
        # yi-9b: 4 kv heads on a 16-way model axis -> replicated
        assert sh.adapt_spec(P("model"), (4,), MESH) == P(None)

    def test_tuple_axes(self):
        got = sh.adapt_spec(P(("pod", "data"), None), (64, 8), POD_MESH)
        assert got == P(("pod", "data"), None)
        got = sh.adapt_spec(P(("pod", "data"), None), (17, 8), POD_MESH)
        assert got == P(None, None)

    def test_rank_extension(self):
        got = sh.adapt_spec(P("model"), (32, 8, 4), MESH)
        assert got == P("model", None, None)


class TestParamSpecs:
    def _specs(self, arch, mesh=MESH, moe_ep=False):
        cfg = get_arch(arch)
        # shapes-only param tree (no allocation)
        from repro.models.model_factory import build_model
        params = jax.eval_shape(build_model(cfg).init, jax.random.PRNGKey(0))
        return cfg, params, sh.param_specs(params, cfg, mesh,
                                           moe_expert_parallel=moe_ep)

    def test_dense_megatron_pattern(self):
        cfg, params, specs = self._specs("deepseek-7b")
        lay = specs["layers"]
        # stacked leading layer dim is never sharded
        assert tuple(lay["attn"]["wq"]) == (None, None, "model")
        assert tuple(lay["attn"]["wo"]) == (None, "model", None)
        assert tuple(lay["mlp"]["w_up"]) == (None, None, "model")
        assert tuple(lay["mlp"]["w_down"]) == (None, "model", None)
        assert tuple(specs["embed"]["embedding"]) == ("model", None)

    def test_gqa_kv_replicated_when_not_divisible(self):
        cfg, params, specs = self._specs("yi-9b")       # kv=4 < 16
        assert tuple(specs["layers"]["attn"]["wk"]) == (None, None, None)
        cfg2, params2, specs2 = self._specs("deepseek-7b")  # kv=32
        assert tuple(specs2["layers"]["attn"]["wk"]) == (None, None, "model")

    def test_moe_expert_parallel_vs_tensor_sharded(self):
        _, _, tp = self._specs("moonshot-v1-16b-a3b", moe_ep=False)
        assert tuple(tp["layers"]["moe"]["moe_up"]) == \
            (None, None, None, "model")
        _, _, ep = self._specs("moonshot-v1-16b-a3b", moe_ep=True)
        # 64 experts % 16 == 0 -> experts dim sharded
        assert tuple(ep["layers"]["moe"]["moe_up"]) == \
            (None, "model", None, None)
        # mixtral: 8 experts % 16 != 0 -> ep falls back to tensor sharding
        _, _, mx = self._specs("mixtral-8x7b", moe_ep=True)
        assert tuple(mx["layers"]["moe"]["moe_up"]) == \
            (None, None, None, "model")

    def test_every_leaf_gets_a_spec(self):
        for arch in ("zamba2-1.2b", "whisper-tiny", "llama-3.2-vision-11b"):
            cfg, params, specs = self._specs(arch)
            n_p = len(jax.tree.leaves(params))
            n_s = len(jax.tree.leaves(
                specs, is_leaf=lambda x: isinstance(x, P)))
            assert n_p == n_s

    def test_fsdp_mode_shards_ff_dim(self):
        sh.set_mode("fsdp")
        try:
            _, _, specs = self._specs("deepseek-7b")
            lay = specs["layers"]
            # ZeRO-3: some weight dim sharded; vocab sharding preserved
            assert "model" in tuple(lay["mlp"]["w_up"])
            assert tuple(specs["embed"]["embedding"]) == ("model", None)
        finally:
            sh.set_mode("tp_sp")


class TestConstrainNoMesh:
    def test_constrain_is_noop_without_mesh(self):
        x = jnp.ones((4, 4))
        assert sh.constrain(x, "data", None) is x


SUBPROCESS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np
import jax
assert len(jax.devices()) == 8
from jax.sharding import Mesh
from repro.configs.base import get_arch, reduced_config, ShapeConfig
from repro.launch import dryrun
from repro.launch.roofline import collective_bytes

cfg = reduced_config(get_arch("deepseek-7b"), num_layers=2, d_model=64,
                     num_heads=4, num_kv_heads=2, head_dim=16)
devs = np.array(jax.devices())

# single-pod-like (2 data x 4 model)
mesh = Mesh(devs.reshape(2, 4), ("data", "model"))
shape = ShapeConfig("t", seq_len=32, global_batch=8, kind="train")
compiled = dryrun._lower_compile(cfg, shape, mesh, moe_ep=False, remat=True)
mem = compiled.memory_analysis()
assert mem is not None
coll, kinds = collective_bytes(compiled.as_text())
assert coll > 0, "expected collectives in a sharded train step"
assert "all-reduce" in kinds, kinds

# multi-pod-like (2 pod x 2 data x 2 model)
mesh2 = Mesh(devs.reshape(2, 2, 2), ("pod", "data", "model"))
compiled2 = dryrun._lower_compile(cfg, shape, mesh2, moe_ep=False,
                                  remat=True)
ca = compiled2.cost_analysis()
assert ca.get("flops", 0) > 0

# decode step shards too
shape_d = ShapeConfig("d", seq_len=64, global_batch=8, kind="decode")
compiled3 = dryrun._lower_compile(cfg, shape_d, mesh, moe_ep=False,
                                  remat=False)

# expert-parallel all_to_all MoE: numerics must match the dense dispatch
# across a REAL multi-device model axis
import dataclasses, jax.numpy as jnp
from repro.models import moe as M, moe_ep, shardings as shx
mcfg = reduced_config(get_arch("moonshot-v1-16b-a3b"))
mcfg = dataclasses.replace(
    mcfg, moe=dataclasses.replace(mcfg.moe, num_experts=8,
                                  capacity_factor=8.0))
p = M.init_moe(jax.random.PRNGKey(0), mcfg)
x = jax.random.normal(jax.random.PRNGKey(1), (4, 16, mcfg.d_model))
shx.set_mesh(mesh)   # (2 data, 4 model); 8 experts % 4 == 0
try:
    y_ref, _ = M.moe_block(p, mcfg, x)
    y_ep, _ = moe_ep.moe_block_ep(p, mcfg, x)
    err = float(jnp.abs(y_ref - y_ep).max())
    assert err < 1e-4, f"EP mismatch on 4-way model axis: {err}"
    a2a = collective_bytes(
        jax.jit(lambda xx: moe_ep.moe_block_ep(p, mcfg, xx)[0])
        .lower(x).compile().as_text())[1]
    assert "all-to-all" in a2a, a2a
finally:
    shx.set_mesh(None)
print("SUBPROCESS_OK")
"""


@pytest.mark.slow
def test_multi_device_lower_compile_subprocess():
    """Real 8-device SPMD compile of train + decode steps on 2D and 3D
    meshes (reduced config). Proves the sharding rules produce a valid
    program, not just valid specs."""
    out = subprocess.run(
        [sys.executable, "-c", SUBPROCESS_SCRIPT],
        capture_output=True, text=True, timeout=900,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": "/root",
             # force the host platform: without this, images with libtpu
             # burn minutes probing TPU metadata endpoints before falling
             # back to CPU
             "JAX_PLATFORMS": "cpu"},
        cwd="/root/repo")
    assert out.returncode == 0, out.stderr[-3000:]
    assert "SUBPROCESS_OK" in out.stdout
