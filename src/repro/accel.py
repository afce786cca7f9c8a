"""Accelerator process plumbing: the compile cache and one chip per process.

Two rules every entry point that compiles follows:

  * ``enable_compile_cache()`` before the first compile. If
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and this
    sets nothing; otherwise the cache lives at one fixed path inside the
    checkout (``<repo>/.jax_cache``, git-ignored). The path is part of the cache key,
    so it never comes from a temporary name, a pid or the time.
  * A TPU chip belongs to one process. A parent that spawns training
    workers stays off JAX, and each spawned training worker sees exactly
    one chip of its own (``one_chip_env``). A run that asks for more
    training workers than the host has chips is refused up front
    (``check_chip_budget``) instead of hanging on libtpu's lock.

Importing this module does not import JAX.
"""
from __future__ import annotations

import glob
import os
import pathlib
from typing import Callable, Dict, Iterable, Optional

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"

# libtpu's default per-process port; pinned workers take consecutive ones
_TPU_PROCESS_PORT = 8476


def enable_compile_cache() -> Optional[str]:
    """Turn on JAX's persistent compilation cache; returns its directory,
    or None for a run held to the CPU (``JAX_PLATFORMS=cpu``): XLA:CPU
    compiles are cheap, and its cached executables reload with
    machine-feature warnings. Touches no backend, so a coordinator that
    must stay off the chip may call it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if path:
        return path                      # JAX picks the variable up itself
    if os.environ.get("JAX_PLATFORMS", "") == "cpu":
        return None
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def host_tpu_chips() -> int:
    """TPU chips attached to this host, counted from its device nodes so
    the caller never initialises JAX (which would take the chips).
    0 when ``JAX_PLATFORMS`` excludes the TPU (CPU runs and tests)."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def check_chip_budget(n_train_workers: int, chips: int) -> None:
    """Refuse a run whose training workers cannot each own a chip."""
    if chips and n_train_workers > chips:
        raise ValueError(
            f"{n_train_workers} training worker processes requested but "
            f"this host has {chips} TPU chip(s); each training worker "
            f"needs a chip of its own (a second process on a held chip "
            f"fails on libtpu's lock or hangs). Use at most {chips} "
            f"training group(s), or --worker-train off.")


def one_chip_env(chip: int) -> Dict[str, str]:
    """libtpu settings that give a process chip ``chip`` and no other.
    One-chip process bounds mark the process as a subset of the host, so
    several such processes may load libtpu side by side."""
    port = _TPU_PROCESS_PORT + chip
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}"}


class ChipSlots:
    """Which chip each spawned training worker of one host owns. A
    restarted group gets its old chip back (its predecessor released it
    on exit); report-only workers and chipless hosts get no pinning."""

    def __init__(self, chips: Optional[int] = None) -> None:
        self.chips = host_tpu_chips() if chips is None else chips
        self._of: Dict[str, int] = {}

    def check(self, specs: Iterable) -> None:
        check_chip_budget(sum(1 for s in specs if s.train), self.chips)

    def env(self, spec) -> Dict[str, str]:
        if not spec.train or not self.chips:
            return {}
        if spec.group not in self._of:
            check_chip_budget(len(self._of) + 1, self.chips)
            self._of[spec.group] = len(self._of)
        return one_chip_env(self._of[spec.group])


def run_with_env(env: Dict[str, str], target: Callable, *args):
    """Spawn-context entry point: apply ``env`` before ``target`` can
    initialise JAX, then run it."""
    os.environ.update(env)
    return target(*args)
