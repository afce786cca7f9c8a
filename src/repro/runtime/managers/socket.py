"""Socket-based execution manager: the multi-host mesh backend.

The coordinator owns one listening TCP socket; every worker — whether a
spawn-context process this manager launches itself (CI mode), or a
standalone ``python -m repro.launch.worker --connect host:port``
process on another machine — dials in and completes the same
rendezvous (DESIGN.md §12):

  worker  -> coordinator   Hello    join request: group + host identity
  coordinator -> worker    Welcome  the authoritative WorkerSpec (batch,
                                    speed tables, fault schedule,
                                    assigned incarnation)
  worker  -> coordinator   Hello    run_worker's opening Hello, stamped
                                    with the assigned incarnation
                                    (consumed by the base-class
                                    handshake, like every transport)

Nothing above the Channel ABC changes: the EventLoop paces StepGrants,
buckets reports and broadcasts Retune row-masks over a SocketChannel
exactly as it does over a Pipe — which is the point. Fig. 6 parity and
bounded-staleness semantics are transport invariants, proven again in
tests/test_runtime_socket.py.

Fault surface (spawn mode — the real thing, like ProcessManager):
  * ``kill``    — SIGKILL. The kernel closes the worker's socket, the
                  coordinator reads EOF: disconnect IS the failure
                  signal, no message needed.
  * ``suspend`` — SIGSTOP. The connection stays open but goes silent —
                  the wedged-node failure mode only silence-derived
                  liveness can see.
  * ``restart`` — a NEW connection completes the rendezvous with an
                  incremented incarnation (reconnect-with-new-
                  incarnation); the predecessor's stale life is
                  distinguishable by that incarnation everywhere.

With ``spawn=False`` the manager launches nothing and waits for
standalone workers to dial in — the genuine two-host mode (a
``restart`` then blocks until a replacement worker connects, e.g. a
supervisor relaunching ``repro.launch.worker`` on the dead host).
"""
from __future__ import annotations

import multiprocessing
import signal
import socket as _socket
import time
from typing import Dict, List, Optional, Tuple

from repro.accel import ChipSlots
from repro.runtime.ipc import ChannelClosed
from repro.runtime.ipc.codec import negotiate
from repro.runtime.ipc.socket import SocketChannel, parse_endpoint
from repro.runtime.managers.base import (ExecutionManager, HandshakeTimeout,
                                         WorkerHandle)
from repro.runtime.managers.process import SpawnedProcessFaults
from repro.runtime.messages import Hello, Welcome
from repro.runtime.worker import WorkerSpec


class SocketExecutionManager(SpawnedProcessFaults, ExecutionManager):
    name = "socket"

    def __init__(self, listen: str = "127.0.0.1:0", spawn: bool = True,
                 hello_timeout: float = 120.0,
                 advertise: Optional[str] = None,
                 codec: Optional[str] = None, chaos=None) -> None:
        """``listen`` is ``host:port`` (port 0 = ephemeral). ``spawn``
        launches one local worker process per spec (CI mode); False
        waits for standalone workers to connect. ``advertise`` is the
        endpoint spawned workers dial (defaults to the bound address,
        with wildcard hosts rewritten to loopback). ``codec`` caps the
        wire-codec negotiation (DESIGN.md §13): None picks the best
        codec each joining worker offers (binary between new builds,
        json for old workers); ``"json"`` forces the compatibility
        baseline for every connection (the CI canary cell). ``chaos``
        activates the fault-injection + reliable-session plane on
        every worker link (DESIGN.md §15); a ChaosSpec or its
        ``--chaos`` string grammar."""
        super().__init__(hello_timeout, chaos=chaos)
        host, port = parse_endpoint(listen, allow_ephemeral=True)
        self._listener = _socket.socket(_socket.AF_INET,
                                        _socket.SOCK_STREAM)
        self._listener.setsockopt(_socket.SOL_SOCKET,
                                  _socket.SO_REUSEADDR, 1)
        self._listener.bind((host, port))
        self._listener.listen(16)
        bound_host, bound_port = self._listener.getsockname()[:2]
        self.endpoint = f"{bound_host}:{bound_port}"
        if advertise is not None:
            self.advertised = advertise
        elif bound_host in ("0.0.0.0", "::", ""):
            self.advertised = f"{_socket.gethostname()}:{bound_port}"
        else:
            self.advertised = self.endpoint
        self._spawn = spawn
        self.codec = codec
        self._ctx = multiprocessing.get_context("spawn")
        self._procs: Dict[str, "multiprocessing.Process"] = {}
        self._chips = ChipSlots()
        # connections whose join-Hello named a group we are not (yet)
        # launching: kept until their spec's _launch claims them
        self._parked: Dict[str, Tuple[SocketChannel, Hello]] = {}

    # -- lifecycle ------------------------------------------------------
    def _launch(self, spec: WorkerSpec) -> WorkerHandle:
        if self._spawn:
            from repro.launch.worker import connect_and_serve

            self._start_proc(spec, connect_and_serve,
                             (self.advertised, spec.group, spec.incarnation),
                             f"stannis-sock-{spec.group}")
        chan, join = self._accept_group(spec.group)
        # same-host workers (spawned, or a standalone that reports our
        # hostname) may ship bulk payloads through the shared-memory
        # plane; cross-host ones stay inline (DESIGN.md §13)
        if join.host and join.host == _socket.gethostname():
            spec.bulk = "shm"
        # codec choice: best of the worker's Hello offer, capped by our
        # configured preference; announced in the Welcome and switched
        # to immediately after — the rendezvous itself is always json
        chosen = negotiate(join.codecs, self.codec)
        chan.put(Welcome(spec.to_wire(), codec=chosen))
        chan.set_codec(chosen)
        handle = WorkerHandle(spec, chan)
        handle.host = join.host
        handle.endpoint = join.endpoint
        return handle

    def _accept_group(self, group: str) -> Tuple[SocketChannel, Hello]:
        """Accept connections until one's join-Hello names ``group``;
        park the rest (standalone workers dial in in arbitrary order)."""
        deadline = time.monotonic() + self.hello_timeout
        while group not in self._parked:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise HandshakeTimeout(
                    f"{group}: no worker connected to {self.endpoint} "
                    f"within {self.hello_timeout:.0f}s")
            self._listener.settimeout(remaining)
            try:
                sock, addr = self._listener.accept()
            except _socket.timeout:
                continue
            except OSError as e:
                raise HandshakeTimeout(f"{group}: listener died: {e}") \
                    from e
            chan = SocketChannel(sock)
            # small per-connection Hello budget: a stray silent
            # connection (port scanner, health check) must not starve
            # genuine workers waiting in the listen backlog for the
            # whole handshake deadline
            hello_wait = min(5.0, max(deadline - time.monotonic(), 0.01))
            if not chan.poll(hello_wait):
                chan.close()             # connected but never said Hello
                continue
            try:
                msg = chan.get()
            except Exception:
                chan.close()
                continue
            if not isinstance(msg, Hello):
                chan.close()
                continue
            msg.endpoint = msg.endpoint or f"{addr[0]}:{addr[1]}"
            old = self._parked.pop(msg.group, None)
            if old is not None:
                old[0].close()           # superseded duplicate join
            self._parked[msg.group] = (chan, msg)
        return self._parked.pop(group)

    # -- mid-run rejoin (self-healing workers, DESIGN.md §15) -----------
    def admit_rejoins(self, batch_sizes: Dict[str, int]) -> List[str]:
        """Non-blocking listener pump the event loop calls every round:
        a standalone worker whose TCP session died reconnects here,
        completes the SAME rendezvous as at start-of-run (its own side
        already bumped the incarnation), and gets the CURRENT plan's
        batch in its Welcome — the tuned plan survives the reconnect
        without operator action."""
        rejoined: List[str] = []
        while True:
            self._listener.settimeout(0.0)
            try:
                sock, addr = self._listener.accept()
            except (BlockingIOError, _socket.timeout):
                break
            except OSError:
                break                    # listener torn down
            chan = SocketChannel(sock)
            if not chan.poll(min(5.0, self.hello_timeout)):
                chan.close()
                continue
            try:
                join = chan.get()
            except Exception:
                chan.close()
                continue
            if not isinstance(join, Hello) or join.group not in self.workers:
                chan.close()             # stranger, or unknown group
                continue
            group = join.group
            old = self.workers[group]
            spec = old.spec
            # the worker declares its own next incarnation (it counted
            # its reconnects); never reuse an already-seen one, or the
            # stale-report guards would conflate the two lives
            spec.incarnation = max(join.incarnation, old.incarnation + 1)
            if group in batch_sizes:
                spec.batch_size = batch_sizes[group]
            join.endpoint = join.endpoint or f"{addr[0]}:{addr[1]}"
            chosen = negotiate(join.codecs, self.codec)
            try:
                chan.put(Welcome(spec.to_wire(), codec=chosen))
            except ChannelClosed:
                chan.close()
                continue
            chan.set_codec(chosen)
            handle = WorkerHandle(spec, chan,
                                  incarnation=spec.incarnation)
            handle.host, handle.endpoint = join.host, join.endpoint
            try:
                self._await_hello(handle)
            except HandshakeTimeout:
                chan.close()
                continue
            if self.chaos is not None:
                handle.channel = self._harden(group, handle.channel)
            try:
                old.channel.close()
            except Exception:
                pass
            self.workers[group] = handle
            rejoined.append(group)
        return rejoined

    # -- fault injection (spawned-process semantics shared with
    # ProcessManager via SpawnedProcessFaults) --------------------------
    def kill(self, group: str) -> None:
        self._kill_proc(group)           # kernel closes its socket: EOF
        self.mark_dead(group)            # external worker: our close=EOF

    def suspend(self, group: str) -> None:
        if not self._signal_proc(group, signal.SIGSTOP):
            raise NotImplementedError(
                "socket manager cannot suspend standalone workers")

    def resume(self, group: str) -> None:
        if not self._signal_proc(group, signal.SIGCONT):
            raise NotImplementedError(
                "socket manager cannot resume standalone workers")

    # -- teardown -------------------------------------------------------
    def shutdown(self) -> None:
        try:
            super().shutdown()
        finally:
            for chan, _ in self._parked.values():
                chan.close()
            self._parked.clear()
            self._listener.close()
