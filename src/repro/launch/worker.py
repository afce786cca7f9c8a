"""Standalone Stannis worker: join a coordinator over TCP.

The multi-host entry point. A worker process on any machine joins a
coordinator (``repro.launch.train --runtime socket --listen``) knowing
only the coordinator's endpoint and its own group name:

    PYTHONPATH=src python -m repro.launch.worker \
        --connect 10.0.0.2:5555 --group csd0

Join handshake (DESIGN.md §12):

  1. connect (with retries — the coordinator may still be binding);
  2. send a join-request ``Hello`` carrying group, pid, hostname and
     this side of the TCP connection (the coordinator's cluster map);
  3. receive ``Welcome`` with the authoritative ``WorkerSpec`` — batch
     size, speed tables, fault schedule, and the incarnation the
     coordinator assigns. No shared filesystem, no pickled closures:
     the spec is wire primitives, JSON-framed;
  4. run the ordinary ``run_worker`` loop (which opens with its own
     Hello, confirming the assigned incarnation) until Shutdown or
     coordinator EOF.

Session resume (DESIGN.md §15): a standalone worker whose TCP
connection dies mid-run does NOT need an operator. ``run_worker``
returns a :class:`~repro.runtime.worker.WorkerExit` carrying every
report the coordinator never acknowledged; ``connect_and_serve`` (with
``resume=True`` — the standalone default) reconnects with exponential
backoff, re-runs the rendezvous under the SAME group with a bumped
incarnation, and replays the carry over the fresh reliable session.
The coordinator's ``admit_rejoins`` pump accepts the new life between
rounds and hands back the CURRENT plan's batch size.

The SAME function (``connect_and_serve``) is the spawn target when
``SocketExecutionManager`` launches workers itself for CI — a spawned
local worker and a standalone remote one are byte-identical on the
wire (spawned workers default ``resume=False``: their manager owns
restarts via fault actions).
"""
from __future__ import annotations

import argparse
import os
import random
import socket as _socket
import time
from typing import Iterator, Optional

from repro.obs import LOG
# parse_endpoint lives with the transport; re-exported here because the
# CLI surface is where users first meet endpoints
from repro.runtime.ipc.codec import supported
from repro.runtime.ipc.socket import SocketChannel, parse_endpoint
from repro.runtime.messages import Hello, Welcome
from repro.runtime.worker import WorkerExit, WorkerSpec, run_worker

__all__ = ["backoff_delays", "connect_and_serve", "main", "parse_endpoint"]

# reconnect backoff (DESIGN.md §15): first retry nearly immediate, then
# exponential up to a cap — a thundering herd of workers rejoining a
# restarted coordinator is decorrelated by the jitter
BACKOFF_BASE = 0.05
BACKOFF_FACTOR = 2.0
BACKOFF_CAP = 2.0


def backoff_delays(base: float = BACKOFF_BASE,
                   factor: float = BACKOFF_FACTOR,
                   cap: float = BACKOFF_CAP,
                   rng: Optional[random.Random] = None) -> Iterator[float]:
    """Yield sleep intervals: exponential growth with half-jitter.

    Each interval is uniform in ``[d/2, d]`` where ``d`` doubles up to
    ``cap`` — the expected total wait stays geometric (fast giving-up
    is preserved) while two workers that died together won't hammer
    the listener in lockstep. ``rng`` is injectable for deterministic
    tests.
    """
    rng = rng if rng is not None else random.Random()
    delay = base
    while True:
        yield delay * (0.5 + 0.5 * rng.random())
        delay = min(delay * factor, cap)


def connect_and_serve(endpoint: str, group: str, incarnation: int = 0,
                      retry_for: float = 30.0,
                      hello_timeout: float = 60.0,
                      resume: bool = False,
                      rng: Optional[random.Random] = None) -> None:
    """Join the coordinator at ``endpoint`` and run the worker loop
    until Shutdown / EOF. Spawn target AND standalone main body.

    With ``resume=True`` a channel loss short of Shutdown triggers a
    rejoin: reconnect (backoff, up to ``retry_for``), same group,
    incarnation + 1, and replay of every unacknowledged report from
    the previous life. A clean Shutdown always ends the loop.
    """
    replay = None
    while True:
        done = _serve_once(endpoint, group, incarnation, retry_for,
                           hello_timeout, replay, rng)
        if done.status == "failed":
            raise SystemExit(f"worker {group}: {done.error}")
        if done.status == "shutdown" or not resume:
            return
        incarnation += 1
        replay = done.carry
        LOG.info("worker_rejoin",
                 f"worker {group}: connection lost, rejoining as "
                 f"incarnation {incarnation} ({len(replay)} unacked "
                 f"to replay)",
                 group=group, incarnation=incarnation,
                 replay=len(replay))


def _serve_once(endpoint: str, group: str, incarnation: int,
                retry_for: float, hello_timeout: float,
                replay, rng: Optional[random.Random]) -> WorkerExit:
    """One life: rendezvous + run_worker. Returns its WorkerExit."""
    host, port = parse_endpoint(endpoint)
    sock = _connect_with_retries(host, port, retry_for, rng=rng)
    chan = SocketChannel(sock)
    try:
        local = "%s:%d" % sock.getsockname()[:2]
        # the join Hello carries this build's codec offer; the
        # rendezvous itself is always json (DESIGN.md §13)
        chan.put(Hello(group, os.getpid(), 0, incarnation,
                       host=_socket.gethostname(), endpoint=local,
                       codecs=supported()))
        if not chan.poll(hello_timeout):
            raise TimeoutError(
                f"worker {group!r}: no Welcome from {endpoint} within "
                f"{hello_timeout:.0f}s")
        msg = chan.get()
        if not isinstance(msg, Welcome):
            raise RuntimeError(
                f"worker {group!r}: expected Welcome, got {msg.kind}")
        chan.set_codec(msg.codec)        # coordinator's pick, from here on
        spec = WorkerSpec.from_wire(msg.spec)
    except Exception:
        chan.close()
        raise
    return run_worker(spec, chan, replay=replay)  # closes the channel


def _connect_with_retries(host: str, port: int, retry_for: float,
                          rng: Optional[random.Random] = None
                          ) -> "_socket.socket":
    deadline = time.monotonic() + retry_for
    delays = backoff_delays(rng=rng)
    while True:
        try:
            return _socket.create_connection((host, port), timeout=10.0)
        except OSError:
            remaining = deadline - time.monotonic()
            if remaining <= 0:
                raise
            time.sleep(min(next(delays), remaining))


def main(argv: Optional[list] = None) -> None:
    ap = argparse.ArgumentParser(
        description="Standalone Stannis worker: join a coordinator "
                    "over TCP (no shared filesystem needed)")
    ap.add_argument("--connect", required=True, metavar="HOST:PORT",
                    help="coordinator endpoint (train.py --listen)")
    ap.add_argument("--group", required=True,
                    help="node-group name this worker serves (must "
                         "match a group in the coordinator's plan)")
    ap.add_argument("--incarnation", type=int, default=0,
                    help="requested incarnation (the coordinator's "
                         "Welcome is authoritative)")
    ap.add_argument("--retry-for", type=float, default=30.0,
                    help="seconds to retry the initial connect (and "
                         "each mid-run reconnect)")
    ap.add_argument("--no-resume", action="store_true",
                    help="exit on connection loss instead of rejoining "
                         "with a bumped incarnation")
    args = ap.parse_args(argv)
    # diagnostics go to stderr (DESIGN.md §14) — stdout stays free for
    # anything a wrapping script captures
    LOG.info("worker_connect",
             f"worker {args.group}: connecting to {args.connect}",
             group=args.group, endpoint=args.connect)
    connect_and_serve(args.connect, args.group, args.incarnation,
                      retry_for=args.retry_for,
                      resume=not args.no_resume)
    LOG.info("worker_done", f"worker {args.group}: done", group=args.group)


if __name__ == "__main__":
    main()
