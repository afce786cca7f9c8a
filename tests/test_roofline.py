"""Roofline extraction utilities: HLO collective parser, three-term math,
ZeRO-1 optimizer sharding specs, hlo_profile aggregation."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import AbstractMesh, PartitionSpec as P

from repro.launch import roofline as rl
from repro.launch.hlo_profile import profile_text, shape_bytes


HLO = """
ENTRY main {
  %p0 = f32[16,4096]{1,0} parameter(0)
  %ag = f32[256,4096]{1,0} all-gather(f32[16,4096]{1,0} %p0), dimensions={0}
  %ar = f32[256,4096]{1,0} all-reduce(f32[256,4096]{1,0} %ag), to_apply=add
  %rs = bf16[16,4096]{1,0} reduce-scatter(bf16[256,4096]{1,0} %x), dimensions={0}
  %a2a = (f32[8,64]{1,0}, f32[8,64]{1,0}) all-to-all(f32[8,64]{1,0} %y, f32[8,64]{1,0} %z)
  %cp = f32[4,4]{1,0} collective-permute(f32[4,4]{1,0} %w)
  %ars = f32[1,2]{1,0} all-reduce-start(f32[1,2]{1,0} %v)
  %ard = f32[1,2]{1,0} all-reduce-done(f32[1,2]{1,0} %ars)
}
"""


class TestCollectiveParser:
    def test_kinds_and_counts(self):
        total, per_kind = rl.collective_bytes(HLO)
        assert per_kind["all-gather"]["count"] == 1
        assert per_kind["all-reduce"]["count"] == 2   # ar + ar-start
        assert per_kind["reduce-scatter"]["count"] == 1
        assert per_kind["all-to-all"]["count"] == 1
        assert per_kind["collective-permute"]["count"] == 1

    def test_byte_math(self):
        total, per_kind = rl.collective_bytes(HLO)
        # output-shape bytes (documented): all-gather output 256x4096 f32
        assert per_kind["all-gather"]["bytes"] == 256 * 4096 * 4
        # bf16 counted at 2 bytes
        assert per_kind["reduce-scatter"]["bytes"] == 16 * 4096 * 2

    def test_done_halves_not_double_counted(self):
        total, per_kind = rl.collective_bytes(HLO)
        # -start counted, -done skipped
        assert per_kind["all-reduce"]["count"] == 2


class TestRooflineMath:
    def mk(self, flops=197e12 * 256, bytes_=0.0, coll=0.0):
        return rl.Roofline(arch="a", shape="s", mesh="m", chips=256,
                           flops=flops, bytes_accessed=bytes_,
                           coll_bytes=coll, per_device_hbm=0.0,
                           model_flops=flops / 2)

    def test_compute_term_one_second_at_peak(self):
        r = self.mk()
        assert r.compute_s == pytest.approx(1.0)
        assert r.bottleneck == "compute"

    def test_memory_term(self):
        r = self.mk(flops=0.0, bytes_=819e9 * 256 * 2)
        assert r.memory_s == pytest.approx(2.0)
        assert r.bottleneck == "memory"

    def test_collective_term_and_roofline_frac(self):
        r = self.mk(coll=50e9 * 256 * 4)
        assert r.collective_s == pytest.approx(4.0)
        assert r.step_s == pytest.approx(4.0)
        # model_flops = peak/2 over 4 s -> 12.5 % of roofline
        assert r.roofline_frac == pytest.approx(0.125)

    def test_model_flops_train_vs_decode(self):
        from repro.configs.base import SHAPES, get_arch
        cfg = get_arch("deepseek-7b")
        tr = rl.model_flops(cfg, SHAPES["train_4k"], "train")
        de = rl.model_flops(cfg, SHAPES["decode_32k"], "decode")
        assert tr == pytest.approx(
            6.0 * cfg.active_param_count() * 256 * 4096)
        assert de == pytest.approx(2.0 * cfg.active_param_count() * 128)

    def test_moe_active_params_smaller_than_total(self):
        from repro.configs.base import get_arch
        cfg = get_arch("mixtral-8x7b")
        assert cfg.active_param_count() < 0.4 * cfg.param_count()


class TestHloProfile:
    def test_shape_bytes(self):
        assert shape_bytes("f32[2,3]") == 24
        assert shape_bytes("bf16[10] f32[2]") == 28
        assert shape_bytes("pred[8]") == 8

    def test_profile_aggregates_by_opcode(self):
        by_op, biggest = profile_text(HLO, top=5)
        assert "all-gather" in by_op
        assert by_op["all-gather"] > 0
        assert len(biggest) <= 5


class TestZero1Specs:
    def test_moments_gain_data_axis(self):
        from repro.launch import specs as sp
        from repro.optim.optimizer import OptState
        mesh = AbstractMesh((16, 16), ("data", "model"))

        params = {"layers": {"wq": jax.ShapeDtypeStruct((32, 4096, 4096),
                                                        jnp.float32)}}
        pshard = {"layers": {"wq": _NS(mesh, P(None, None, "model"))}}
        opt_shape = OptState(
            step=jax.ShapeDtypeStruct((), jnp.int32),
            mu=params, nu=params,
            grad_norm=jax.ShapeDtypeStruct((), jnp.float32), ef=None)
        base = sp.opt_shardings(opt_shape, pshard, mesh, zero1=False)
        z1 = sp.opt_shardings(opt_shape, pshard, mesh, zero1=True)
        assert tuple(base.mu["layers"]["wq"].spec) == (None, None, "model")
        # zero1: stacked-layer dim (32 % 16 == 0) picked up the data axis
        assert tuple(z1.mu["layers"]["wq"].spec) == ("data", None, "model")
        assert tuple(z1.nu["layers"]["wq"].spec) == ("data", None, "model")


def _NS(mesh, spec):
    from jax.sharding import NamedSharding
    return NamedSharding(mesh, spec)
