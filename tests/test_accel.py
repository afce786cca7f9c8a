"""Accelerator process plumbing: the compile-cache rule and one chip per
training process (repro.accel)."""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import pytest

from repro import accel
from repro.runtime import ProcessManager, WorkerSpec

SRC = os.path.join(os.path.dirname(os.path.dirname(__file__)), "src")


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


class TestCompileCache:
    def test_env_variable_wins_and_nothing_is_set(self, monkeypatch,
                                                  restore_cache_dir):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        before = jax.config.jax_compilation_cache_dir
        assert accel.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_in_checkout_path_when_unset(self, monkeypatch,
                                               restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "tpu")
        path = accel.enable_compile_cache()
        assert path == str(accel.CACHE_DIR)
        assert jax.config.jax_compilation_cache_dir == path
        assert accel.CACHE_DIR.name == ".jax_cache"
        assert (accel.CACHE_DIR.parent / "src" / "repro").is_dir()

    def test_cpu_only_runs_keep_the_cache_off(self, monkeypatch,
                                              restore_cache_dir):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        before = jax.config.jax_compilation_cache_dir
        assert accel.enable_compile_cache() is None
        assert jax.config.jax_compilation_cache_dir == before

    def test_entries_land_in_the_named_directory(self, tmp_path):
        """A compile after enable_compile_cache() writes its entry under
        JAX_COMPILATION_CACHE_DIR and nowhere else."""
        cache = tmp_path / "cache"
        code = (
            "import jax, jax.numpy as jnp\n"
            "from repro.accel import enable_compile_cache\n"
            "print(enable_compile_cache())\n"
            "jax.config.update('jax_persistent_cache_min_compile_time_secs',"
            " 0)\n"
            "jax.jit(lambda x: jnp.sin(x) @ x)(jnp.ones((64, 64)))"
            ".block_until_ready()\n")
        env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
                   JAX_PLATFORMS="cpu", PYTHONPATH=SRC)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             cwd=tmp_path, capture_output=True, text=True,
                             timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == str(cache)
        assert any(p.is_file() for p in cache.rglob("*"))
        assert sorted(p.name for p in tmp_path.iterdir()) == ["cache"]


class TestChipBudget:
    def test_host_chips_zero_when_held_to_cpu(self, monkeypatch):
        monkeypatch.setenv("JAX_PLATFORMS", "cpu")
        assert accel.host_tpu_chips() == 0

    @pytest.mark.parametrize("workers,chips,ok", [
        (1, 1, True), (4, 4, True), (2, 1, False), (5, 4, False),
        (8, 0, True),                 # no chips: CPU workers, no limit
    ])
    def test_check_chip_budget(self, workers, chips, ok):
        if ok:
            accel.check_chip_budget(workers, chips)
        else:
            with pytest.raises(ValueError, match="needs a chip of its own"):
                accel.check_chip_budget(workers, chips)

    def test_one_chip_env_isolates_each_chip(self):
        envs = [accel.one_chip_env(i) for i in range(4)]
        assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
        assert all(e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
                   and e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
        assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4

    def test_chip_slots_pin_training_workers_only(self):
        slots = accel.ChipSlots(chips=2)
        train = {"arch": "deepseek-7b"}
        a = WorkerSpec("a", 1, 1, train=train)
        b = WorkerSpec("b", 1, 1, train=train)
        idle = WorkerSpec("r", 1, 1)
        assert slots.env(idle) == {}
        assert slots.env(a)["TPU_VISIBLE_CHIPS"] == "0"
        assert slots.env(b)["TPU_VISIBLE_CHIPS"] == "1"
        # a restarted group gets its own chip back
        assert slots.env(a)["TPU_VISIBLE_CHIPS"] == "0"
        with pytest.raises(ValueError):
            slots.env(WorkerSpec("c", 1, 1, train=train))

    def test_chipless_host_pins_nothing(self):
        spec = WorkerSpec("a", 1, 1, train={"arch": "deepseek-7b"})
        assert accel.ChipSlots(chips=0).env(spec) == {}

    def test_run_with_env_applies_before_target(self, monkeypatch):
        monkeypatch.delenv("REPRO_TEST_MARK", raising=False)
        seen = accel.run_with_env({"REPRO_TEST_MARK": "7"},
                                  lambda k: os.environ.get(k),
                                  "REPRO_TEST_MARK")
        assert seen == "7"

    def test_process_manager_refuses_before_spawning(self):
        """More training workers than chips: refused up front, nothing
        spawned (not a hang on the second process's chip)."""
        manager = ProcessManager()
        manager._chips = accel.ChipSlots(chips=1)
        train = {"arch": "deepseek-7b", "seq_len": 8, "reduced": True}
        specs = [WorkerSpec(g, 1, 1, train=train) for g in ("a", "b")]
        with pytest.raises(ValueError, match="1 TPU chip"):
            manager.start(specs)
        assert manager._procs == {}
