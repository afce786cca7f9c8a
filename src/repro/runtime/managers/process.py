"""Process-based execution manager: real workers, real faults.

Each node group runs in its own spawn-context process (spawn, not fork:
workers may initialize JAX, which must not inherit a forked runtime).
Specs travel as wire primitives and the transport Connection is
inherited through ``Process(args=...)`` — nothing closure-shaped
crosses the boundary.

Fault injection is the real thing:
  * ``kill``    — SIGKILL + join. The coordinator sees channel EOF and,
                  through bus silence, the liveness mask-out path.
  * ``suspend`` — SIGSTOP. The channel stays open but goes silent: the
                  exact failure mode of a wedged node, which only the
                  silence-derived liveness path can detect.
  * ``resume``  — SIGCONT. The worker drains its grant backlog (stale
                  reports are discarded by the event loop) and rejoins
                  at its knee.
"""
from __future__ import annotations

import multiprocessing
import os
import signal

from repro.accel import ChipSlots, run_with_env
from repro.runtime.ipc.pipe import PipeChannel
from repro.runtime.ipc.shm import shm_available
from repro.runtime.managers.base import ExecutionManager, WorkerHandle
from repro.runtime.worker import WorkerSpec, worker_entry


class SpawnedProcessFaults:
    """Shared fault surface for managers whose workers are spawn-context
    processes (``self._procs``: {group: Process}) — the SIGKILL + join,
    SIGSTOP/SIGCONT, and join-then-force-stop teardown semantics live
    here ONCE, for both the pipe (ProcessManager) and socket
    (SocketExecutionManager) transports.

    Chips, too: on a TPU host each spawned training worker is given one
    chip of its own (``self._chips``), and a start that asks for more
    training workers than the host has chips is refused before anything
    spawns."""

    _procs: dict
    _chips: ChipSlots
    _spawn: bool = True

    def start(self, specs) -> None:
        specs = list(specs)
        if self._spawn:
            self._chips.check(specs)
        super().start(specs)

    def _start_proc(self, spec: WorkerSpec, target, args, name: str):
        proc = self._ctx.Process(
            target=run_with_env, args=(self._chips.env(spec), target, *args),
            name=name, daemon=True)
        proc.start()
        self._procs[spec.group] = proc
        return proc

    def _kill_proc(self, group: str) -> None:
        proc = self._procs.get(group)
        if proc is not None and proc.is_alive():
            proc.kill()
            proc.join(timeout=10.0)

    def _signal_proc(self, group: str, sig: int) -> bool:
        """Signal the group's spawned process if it exists; False when
        the group has no local process (e.g. a standalone socket
        worker, which the caller cannot signal)."""
        proc = self._procs.get(group)
        if proc is None:
            return False
        if proc.pid and proc.is_alive():
            os.kill(proc.pid, sig)
        return True

    def _join_all(self) -> None:
        for proc in self._procs.values():
            proc.join(timeout=10.0)
            if proc.is_alive():                  # wedged: force-stop
                proc.kill()
                proc.join(timeout=5.0)


class ProcessManager(SpawnedProcessFaults, ExecutionManager):
    name = "process"

    def __init__(self, hello_timeout: float = 120.0, chaos=None) -> None:
        super().__init__(hello_timeout, chaos=chaos)
        self._ctx = multiprocessing.get_context("spawn")
        self._procs = {}
        self._chips = ChipSlots()

    def _launch(self, spec: WorkerSpec) -> WorkerHandle:
        if shm_available():
            # spawned workers share this host by construction: bulk
            # payloads (checkpoint state blobs) go through the
            # shared-memory ring, not the pipe (DESIGN.md §13)
            spec.bulk = "shm"
        coord_conn, worker_conn = self._ctx.Pipe()
        self._start_proc(spec, worker_entry, (spec.to_wire(), worker_conn),
                         f"stannis-{spec.group}")
        worker_conn.close()                      # child's end only
        return WorkerHandle(spec, PipeChannel(coord_conn))

    def kill(self, group: str) -> None:
        self._kill_proc(group)
        self.mark_dead(group)

    def suspend(self, group: str) -> None:
        self._signal_proc(group, signal.SIGSTOP)

    def resume(self, group: str) -> None:
        self._signal_proc(group, signal.SIGCONT)
