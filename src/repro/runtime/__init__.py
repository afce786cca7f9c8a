"""Stannis runtime: a multi-process distributed execution subsystem.

The paper's Stannis framework is a *distributed* orchestrator — a
master spawning training on heterogeneous nodes, collecting per-step
speed reports and pushing retuned batch sizes back out. This package is
that execution substrate (DESIGN.md §10):

  messages.py   typed coordinator<->worker wire protocol
  ipc/          channels over multiprocessing Pipe / Queue + TCP sockets
  worker.py     the worker loop (+ speed governor, real jitted steps)
  managers/     thread-, process- and socket-based worker lifecycles
  eventloop.py  the coordinator, owning the existing ControlPlane
  parity.py     sim/runtime trace-parity harness
"""
from repro.runtime.eventloop import (EventLoop, FaultAction,
                                     RetuneLagTracker, RoundStats,
                                     RuntimeResult, WorkerFailed,
                                     specs_from_plan)
from repro.runtime.managers import (MANAGERS, ExecutionManager, LocalManager,
                                    ProcessManager, SocketExecutionManager)
from repro.runtime.messages import (CheckpointAck, CheckpointRequest, Goodbye,
                                    Hello, Message, Retune, Shutdown,
                                    StepGrant, StepReportMsg, Welcome)
from repro.runtime.worker import (InterferenceSpec, SpeedGovernor,
                                  WorkerSpec, run_worker, worker_entry)

__all__ = [
    "EventLoop", "FaultAction", "RetuneLagTracker", "RoundStats",
    "RuntimeResult", "WorkerFailed", "specs_from_plan",
    "MANAGERS", "ExecutionManager", "LocalManager", "ProcessManager",
    "SocketExecutionManager",
    "CheckpointAck", "CheckpointRequest", "Goodbye", "Hello", "Message",
    "Retune", "Shutdown", "StepGrant", "StepReportMsg", "Welcome",
    "InterferenceSpec", "SpeedGovernor", "WorkerSpec", "run_worker",
    "worker_entry",
]
