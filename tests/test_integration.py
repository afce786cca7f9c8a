"""End-to-end system behaviour: live hetero training on CPU with the full
stack (pipeline -> jitted step -> controller -> retune -> checkpoint ->
elastic), and serving."""
from __future__ import annotations

import jax
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced_config
from repro.core.allocator import solve
from repro.core.speed_model import SpeedModel
from repro.launch.serve import Server
from repro.launch.train import (HeteroTrainer, TrainerConfig,
                                dropout_report_fn, interference_report_fn,
                                parse_interfere, probe_in_child,
                                train_inproc)


def tiny_cfg(arch="deepseek-7b", **kw):
    return reduced_config(get_arch(arch), **kw)


def small_plan(counts=(1, 2), caps=None):
    sm = SpeedModel(np.array([1.0, 2, 4, 8]), np.array([10.0, 18, 28, 30]))
    groups = {}
    for i, c in enumerate(counts):
        spec = (c, sm) if caps is None else (c, sm, caps[i])
        groups[f"g{i}"] = spec
    return solve(groups, dataset_size=4096)


def trainer_cfg(tmp_path=None, **kw):
    from repro.optim.optimizer import OptConfig
    kw.setdefault("seq_len", 16)
    kw.setdefault("steps", 12)
    kw.setdefault("log_every", 0)
    kw.setdefault("dataset_size", 4096)
    kw.setdefault("opt", OptConfig(lr=5e-3, warmup_steps=0,
                                   schedule="const"))
    if tmp_path is not None:
        kw.setdefault("ckpt_dir", str(tmp_path / "ckpt"))
    return TrainerConfig(**kw)


class TestEndToEnd:
    def test_healthy_run_trains(self):
        t = HeteroTrainer(tiny_cfg(), small_plan(), trainer_cfg())
        recs = t.run(12)
        assert len(recs) == 12
        assert all(np.isfinite(r.loss) for r in recs)
        assert recs[-1].loss < recs[0].loss          # learning happens
        assert not any(r.retune for r in recs)       # no spurious retunes

    def test_interference_triggers_retune_and_training_continues(self):
        t = HeteroTrainer(tiny_cfg(), small_plan(), trainer_cfg(steps=25))
        fn = interference_report_fn({"g1": [(5, 10 ** 9, 0.45)]})
        recs = t.run(25, report_fn=fn)
        retunes = [r for r in recs if r.retune and r.retune.startswith("g1")]
        assert retunes, "HyperTune never fired under interference"
        # retune fires after the 5-step hysteresis, not instantly
        assert retunes[0].step >= 5 + 4
        # batch shrank on the interfered group, shapes static
        assert t.controller.plan.batch_sizes()["g1"] < \
            small_plan().batch_sizes()["g1"]
        assert all(np.isfinite(r.loss) for r in recs)
        # global batch after retune is smaller but nonzero
        assert 0 < t.controller.plan.global_batch <= \
            small_plan().global_batch

    def test_mask_reaches_jitted_step_without_recompile(self):
        t = HeteroTrainer(tiny_cfg(), small_plan(), trainer_cfg(steps=25))
        fn = interference_report_fn({"g1": [(5, 10 ** 9, 0.45)]})
        t.run(25, report_fn=fn)
        assert t.step_fn._cache_size() == 1          # one compile, ever

    def test_group_dropout_masks_out_and_rejoins(self):
        t = HeteroTrainer(tiny_cfg(), small_plan(), trainer_cfg(steps=30))
        fn = dropout_report_fn({"g1": (5, 18)})
        recs = t.run(30, report_fn=fn)
        # heartbeat declared g1 dead -> batch 0
        dead_evt = [e for e in t.controller.events if e.new_batch == 0]
        assert dead_evt and dead_evt[0].group == "g1"
        # training continued while g1 was dead
        dead_recs = [r for r in recs if dead_evt[0].step < r.step < 18]
        assert dead_recs and all(np.isfinite(r.loss) for r in dead_recs)
        assert all(r.global_batch > 0 for r in dead_recs)
        # rejoin: batch restored after reports resume
        assert t.controller.plan.batch_sizes()["g1"] > 0

    def test_private_data_never_leaves_home_group(self):
        cfg = trainer_cfg(private_frac=0.4, steps=6)
        t = HeteroTrainer(tiny_cfg(), small_plan(), cfg)
        layout_rows = {}
        start = 0
        for g in t.plan.groups:
            rows = g.capacity * g.count
            layout_rows[g.name] = (start, start + rows)
            start += rows
        for _ in range(6):
            b = t.pipeline.next_batch()
            live = np.flatnonzero(b["sample_mask"])
            for i in live:
                if b["private"][i]:
                    gi = int(b["owners"][i])
                    lo, hi = layout_rows[t.plan.groups[gi].name]
                    assert lo <= i < hi


class TestCheckpointResume:
    def test_resume_is_bitwise_deterministic(self, tmp_path):
        cfg_a = trainer_cfg(tmp_path, steps=10, ckpt_every=5)
        ref = HeteroTrainer(tiny_cfg(), small_plan(), cfg_a)
        ref.run(10)
        ref_params = jax.tree.map(np.asarray, ref.params)

        # crash after 5 steps
        tmp2 = tmp_path / "b"
        tmp2.mkdir()
        cfg_b = trainer_cfg(tmp2, steps=10, ckpt_every=5)
        crash = HeteroTrainer(tiny_cfg(), small_plan(), cfg_b)
        crash.run(5)
        del crash

        # new process stand-in: fresh trainer, auto-resume, finish
        resumed = HeteroTrainer(tiny_cfg(), small_plan(), cfg_b)
        assert resumed.resume()
        assert resumed.step == 5
        resumed.run(5)
        for a, b in zip(jax.tree.leaves(ref_params),
                        jax.tree.leaves(jax.tree.map(np.asarray,
                                                     resumed.params))):
            np.testing.assert_array_equal(a, b)

    def test_resume_restores_retuned_plan(self, tmp_path):
        cfg = trainer_cfg(tmp_path, steps=20, ckpt_every=20)
        t = HeteroTrainer(tiny_cfg(), small_plan(), cfg)
        fn = interference_report_fn({"g1": [(2, 10 ** 9, 0.45)]})
        t.run(20, report_fn=fn)
        shrunk = t.controller.plan.batch_sizes()["g1"]
        assert shrunk < small_plan().batch_sizes()["g1"]

        t2 = HeteroTrainer(tiny_cfg(), small_plan(), cfg)
        assert t2.resume()
        assert t2.controller.plan.batch_sizes()["g1"] == shrunk

    def test_no_checkpoint_resume_returns_false(self, tmp_path):
        cfg = trainer_cfg(tmp_path)
        t = HeteroTrainer(tiny_cfg(), small_plan(), cfg)
        assert not t.resume()


class TestProbe:
    def test_probe_speed_model_monotone_nondegenerate(self):
        t = HeteroTrainer(tiny_cfg(), small_plan(),
                          trainer_cfg(steps=1, seq_len=8))
        sm = t.probe_speed_model(batch_ladder=(1, 4, 8), iters=1)
        assert sm.vmax > 0
        assert sm.speed(8) >= sm.speed(1) * 0.5   # timing noise tolerated

    def test_probe_in_child_returns_the_curve(self):
        """The coordinator-side probe of a process run: a spawned child
        times the ladder and exits; the caller gets only the curve."""
        sm = probe_in_child(tiny_cfg(), trainer_cfg(seq_len=8))
        assert list(sm.batch_sizes) == [1, 2, 4, 8]
        assert sm.vmax > 0


class TestTrainInproc:
    """The inproc main path shared by the train CLI and chip_smoke.py."""

    def test_probe_allocate_train_retune_without_recompile(self):
        ivs, drops = parse_interfere("worker@2x0.4")
        t = train_inproc(tiny_cfg(), trainer_cfg(steps=10), "host:1,worker:2",
                         ivs, drops, batch_ladder=(1, 2, 4, 8))
        assert len(t.records) == 10
        assert all(np.isfinite(r.loss) for r in t.records)
        assert [r for r in t.records if r.retune], "no retune"
        assert t.step_fn._cache_size() == 1
        assert set(t.plan.batch_sizes()) == {"host", "worker"}

    def test_given_state_is_trained_not_copied(self):
        """One copy of the model state: a trainer built with the probing
        trainer's (params, opt_state) holds those very arrays."""
        boot = HeteroTrainer.for_probe(tiny_cfg(), trainer_cfg())
        t = HeteroTrainer(tiny_cfg(), small_plan(), trainer_cfg(),
                          state=(boot.params, boot.opt_state))
        assert all(a is b for a, b in zip(jax.tree.leaves(t.params),
                                          jax.tree.leaves(boot.params)))
        assert t.opt_state is boot.opt_state


class TestServe:
    @pytest.mark.parametrize("arch", ["deepseek-7b", "mamba2-1.3b",
                                      "mixtral-8x7b"])
    def test_generate_shapes_and_determinism(self, arch):
        cfg = tiny_cfg(arch)
        srv = Server(cfg, batch=2, max_len=24)
        rng = np.random.default_rng(0)
        prompts = rng.integers(0, cfg.vocab_size, (2, 6))
        out1 = srv.generate(prompts, steps=8)
        out2 = srv.generate(prompts, steps=8)
        assert out1["tokens"].shape == (2, 8)
        np.testing.assert_array_equal(out1["tokens"], out2["tokens"])
        assert (out1["tokens"] < cfg.vocab_size).all()
