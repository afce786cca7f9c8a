"""Stannis runtime worker: one node group's training loop.

The SAME loop body serves both execution managers — a LocalManager
thread and a ProcessManager spawn-context process run ``run_worker``
unchanged; only the transport and the fault surface differ. The worker:

  * announces itself with ``Hello`` (join / rejoin);
  * on each ``StepGrant`` optionally runs ONE real jitted train step
    (``hetero_dp.make_train_step`` at the group's live batch size inside
    its fixed-capacity row mask) and reports its speed. Under
    bounded-staleness pacing (``StepGrant.staleness`` > 0) several
    grants sit queued in the channel at once; the loop drains them
    FIFO, running ahead of the coordinator's control rounds while
    stamping every report with ITS OWN granted step — a ``Retune``
    queued behind k outstanding grants therefore lands exactly k+1
    steps after the decision, which is the determinism the sim mirror
    (``ClusterSim(staleness=k)``) and the trace-parity tests rely on;
  * applies ``Retune`` messages by flipping row-mask contents only —
    the compiled step is untouched (``CheckpointAck.n_compiles`` proves
    it);
  * carries its own interference injector (:class:`SpeedGovernor`) —
    the Gzip core-stealing scenarios of the paper, applied worker-side
    so the coordinator observes a genuinely degraded report stream.

Module import stays JAX-free: spawn-context workers that only report
(trace-parity runs) never pay the jax import, and ``TrainExecutor``
imports it lazily.
"""
from __future__ import annotations

import collections
import dataclasses
import json
import os
import socket as _socket
import time
import traceback
from typing import Deque, Dict, List, Optional, Tuple

import numpy as np

from repro.core.interference import (govern_speed, window_capacity,
                                     window_speed_cap)
from repro.core.speed_model import SpeedModel
from repro.obs import LOG, NULL_TRACER, Tracer
from repro.runtime.ipc import (Channel, ChannelClosed, CorruptFrame,
                               DEFAULT_RESYNC_BUDGET, ReliableChannel)
from repro.runtime.ipc.shm import (BulkUnavailable, ShmBulkPlane,
                                   publish_bulk, shm_available)
from repro.runtime.messages import (CheckpointAck, CheckpointRequest, Goodbye,
                                    Hello, Message, ReportBatch, Retune,
                                    Shutdown, StepGrant, StepReportMsg)

# speed samples kept worker-side for the checkpoint state blob
_SPEED_HISTORY = 256


@dataclasses.dataclass
class InterferenceSpec:
    """Worker-side interference window, mirroring
    ``core.simulator.Interference`` field-for-field so the governed
    report stream is bit-identical to the simulator's."""

    start_step: int
    end_step: int
    capacity: float = 1.0
    speed_cap: Optional[float] = None


@dataclasses.dataclass
class WorkerSpec:
    """Everything a worker needs, as primitives (spawn-safe).

    ``silence`` windows make the worker skip reporting (alive but mute)
    — the deterministic fault injector for thread workers, which cannot
    be SIGKILLed. ``train`` enables the real jitted step:
    ``{"arch": name, "seq_len": int, "reduced": bool}``.
    ``step_delay_s`` models per-step compute time for report-only
    workers (a real TrainExecutor has it for free): the worker sleeps
    that long per granted step, releasing the GIL, so thread-worker
    benchmarks exhibit the genuine compute/coordination overlap that
    bounded-staleness pacing exists to exploit.

    ``bulk`` selects the bulk data path (DESIGN.md §13): ``"shm"`` lets
    the worker publish bulk payloads (checkpoint state blobs) through a
    shared-memory ring instead of inline in the control frame —
    managers set it for workers they know share the coordinator's host;
    ``"inline"`` (the default, and the cross-host fallback) keeps every
    byte in the frame.

    ``obs`` (DESIGN.md §14) turns on worker-side tracing: step spans,
    governor throttle events and retune-applied instants, accumulated
    in a local ring and shipped back piggybacked on the report/ack
    traffic the worker was sending anyway. Off by default — a
    non-tracing worker's wire frames are byte-identical to the pre-obs
    protocol — and dropped by ``from_wire`` on builds that predate it.
    """

    group: str
    batch_size: int
    capacity: int
    count: int = 1
    speed_batches: List[float] = dataclasses.field(default_factory=list)
    speed_speeds: List[float] = dataclasses.field(default_factory=list)
    interference: List[InterferenceSpec] = dataclasses.field(
        default_factory=list)
    silence: List[Tuple[int, int]] = dataclasses.field(default_factory=list)
    train: Optional[Dict] = None
    seed: int = 0
    incarnation: int = 0
    step_delay_s: float = 0.0
    bulk: str = "inline"
    obs: bool = False
    # DESIGN.md §15: the coordinator runs this link through the chaos
    # plane — wrap the transport in a ReliableChannel right after the
    # Hello, mirroring the coordinator side. Dropped by from_wire on
    # pre-chaos builds (which a chaos-enabled coordinator should not
    # pair with anyway).
    session: bool = False

    def to_wire(self) -> Dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_wire(cls, wire: Dict) -> "WorkerSpec":
        # drop unknown keys: a NEWER coordinator's Welcome may carry
        # spec fields this build predates — they are tuning hints, not
        # contract, and must not break the join
        names = {f.name for f in dataclasses.fields(cls)}
        wire = {k: v for k, v in wire.items() if k in names}
        wire["interference"] = [InterferenceSpec(**iv)
                                for iv in wire.get("interference", [])]
        wire["silence"] = [tuple(w) for w in wire.get("silence", [])]
        return cls(**wire)

    def speed_model(self) -> SpeedModel:
        return SpeedModel(np.asarray(self.speed_batches, float),
                          np.asarray(self.speed_speeds, float))


class SpeedGovernor:
    """Worker-side interference injector: the SAME window math as
    ``ClusterSim`` (one shared copy in ``core.interference`` — parity
    depends on it), evaluated against the coordinator's logical clock
    (the grant step)."""

    def __init__(self, windows: List[InterferenceSpec],
                 silence: List[Tuple[int, int]]) -> None:
        self.windows = windows
        self.silence = silence

    def capacity(self, step: int) -> float:
        return window_capacity(self.windows, step)

    def speed_cap(self, step: int) -> Optional[float]:
        return window_speed_cap(self.windows, step)

    def silenced(self, step: int) -> bool:
        return any(s <= step < e for s, e in self.silence)

    def govern(self, raw_speed: float, step: int) -> float:
        return govern_speed(raw_speed, self.windows, step)


class TrainExecutor:
    """Real training substrate: a model + jitted ``make_train_step``, run
    at the group's live batch size inside its capacity-row mask. Built
    lazily so report-only workers never import jax.

    ``spec.train``: ``arch`` (registered name), ``seq_len``, ``reduced``
    (default True: the tiny CPU config) and optional ``overrides``, a
    dict of :class:`ArchConfig` fields replaced after that (e.g. a depth
    or vocabulary cut of a full-width config)."""

    def __init__(self, spec: WorkerSpec) -> None:
        import jax
        import jax.numpy as jnp

        from repro.accel import enable_compile_cache
        from repro.configs.base import get_arch, reduced_config
        from repro.core import hetero_dp
        from repro.models.model_factory import aux_inputs, build_model
        from repro.optim.optimizer import AdamW, OptConfig

        enable_compile_cache()
        cfg = get_arch(spec.train["arch"])
        if spec.train.get("reduced", True):
            cfg = reduced_config(cfg)
        cfg = dataclasses.replace(cfg, **spec.train.get("overrides", {}))
        self.seq_len = int(spec.train.get("seq_len", 32))
        self.capacity = max(spec.capacity, 1)
        self.model = build_model(cfg)
        self.opt = AdamW(OptConfig())
        self.params = self.model.init(jax.random.PRNGKey(spec.seed))
        self.opt_state = self.opt.init(self.params)
        self.step_fn = jax.jit(hetero_dp.make_train_step(self.model, self.opt))
        rng = np.random.default_rng(spec.seed)
        toks = rng.integers(0, cfg.vocab_size,
                            (self.capacity, self.seq_len + 1))
        self._batch = {
            "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32),
        }
        self._batch.update(aux_inputs(cfg, self.capacity, self.seq_len,
                                      jnp.float32, concrete=True))
        self._jnp = jnp
        self._jax = jax
        self.losses: Deque[List[float]] = collections.deque(
            maxlen=_SPEED_HISTORY)       # [batch_size, loss] per step
        dev = jax.devices()[0]
        self.device = {"platform": dev.platform, "kind": dev.device_kind,
                       "id": dev.id,
                       "coords": list(getattr(dev, "coords", None) or []),
                       "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS",
                                                       ""),
                       "count": len(jax.devices())}
        LOG.info("worker_device",
                 f"worker {spec.group}: {dev.platform} device {dev.id} "
                 f"({dev.device_kind}) of {len(jax.devices())} visible",
                 group=spec.group, **self.device)

    def run_step(self, batch_size: int) -> Tuple[float, float]:
        """One jitted step with the first ``batch_size`` capacity rows
        live. Returns (loss, wall_dt)."""
        jnp = self._jnp
        mask = np.zeros((self.capacity,), np.float32)
        mask[:min(batch_size, self.capacity)] = 1.0
        batch = dict(self._batch, sample_mask=jnp.asarray(mask))
        t0 = time.perf_counter()
        self.params, self.opt_state, metrics = self.step_fn(
            self.params, self.opt_state, batch)
        loss = float(metrics["loss"])            # blocks
        self.losses.append([batch_size, loss])
        return loss, max(time.perf_counter() - t0, 1e-9)

    def summary(self) -> Dict:
        """What the checkpoint state blob reports of this executor: the
        device it trains on and its (batch size, loss) history."""
        peak = (self._jax.devices()[0].memory_stats() or {}).get(
            "peak_bytes_in_use")
        return {"device": dict(self.device, peak_bytes_in_use=peak),
                "losses": list(self.losses)}

    @property
    def n_compiles(self) -> int:
        return int(self.step_fn._cache_size())


@dataclasses.dataclass
class WorkerExit:
    """Why :func:`run_worker` returned, and what it could not deliver.

    ``status`` is ``"shutdown"`` (orderly, coordinator said so),
    ``"closed"`` (the channel died under the worker) or ``"failed"``
    (the training executor could not be built; ``error`` says why, and
    the coordinator was told in a ``Goodbye``). ``carry`` is the
    undelivered backlog — unflushed pending reports plus, on a session
    channel, every frame the coordinator never acked — which a
    self-healing socket worker replays through its NEXT incarnation's
    session (``launch/worker.py``), so a TCP reset loses nothing."""

    status: str
    carry: List[Message] = dataclasses.field(default_factory=list)
    error: str = ""


def run_worker(spec: WorkerSpec, chan: Channel,
               replay: Optional[List[Message]] = None) -> WorkerExit:
    """The worker loop (thread and process entry point share it).

    The TrainExecutor is built on the FIRST StepGrant, not before the
    Hello: the handshake must never wait on model init / jit compile
    (a manager's ``hello_timeout`` is a liveness bound, while the
    compile stall is already covered by the coordinator's generous
    ``round_timeout`` for training runs).

    Report coalescing (DESIGN.md §13): under bounded-staleness pacing
    several grants sit queued in the channel at once; instead of
    answering each with its own frame, the loop holds finished reports
    in ``pending`` while MORE input is already queued (``poll(0.0)``)
    and flushes once the backlog is drained — one ReportBatch frame for
    the whole run-ahead window. The flush also happens before answering
    any non-grant message, so a CheckpointAck can never overtake the
    reports of rounds the worker already ran. At staleness 0 the input
    queue is empty after every grant, each report flushes alone as a
    plain StepReportMsg, and the wire is byte-identical to the
    pre-coalescing protocol — which is what keeps the synchronous
    parity traces exact."""
    gov = SpeedGovernor(spec.interference, spec.silence)
    sm = spec.speed_model()
    executor: Optional[TrainExecutor] = None
    worker_step = 0
    pending: List[StepReportMsg] = []
    speed_history: Deque[float] = collections.deque(maxlen=_SPEED_HISTORY)
    bulk_plane: Optional[ShmBulkPlane] = None
    speed_memo: Dict[float, float] = {}  # batch -> curve speed (pure fn)
    # worker-side trace ring (DESIGN.md §14): small — it drains into
    # every outgoing report/ack, so depth only matters across one
    # run-ahead window. NULL_TRACER is falsy: every `if tr:` below is a
    # dead branch for the (default) untraced worker.
    tr = Tracer(source=spec.group, capacity=2048) if spec.obs else NULL_TRACER

    def flush() -> None:
        if not pending:
            return
        out = pending[0] if len(pending) == 1 else ReportBatch.pack(pending)
        if tr:
            out.obs = tr.drain_wire() or None
        chan.put(out)
        pending.clear()

    exit_status = "closed"
    error = ""
    try:
        chan.put(Hello(spec.group, os.getpid(), spec.batch_size,
                       spec.incarnation, host=_socket.gethostname()))
        if spec.session:
            # chaos-hardened link (DESIGN.md §15): tolerate a bounded
            # streak of undecodable frames, and speak the reliable
            # session dialect from the first post-Hello frame — the
            # coordinator wraps its end right after consuming the Hello
            chan.resync_budget = DEFAULT_RESYNC_BUDGET
            chan = ReliableChannel(chan)
            for m in (replay or []):     # previous incarnation's backlog
                chan.put(m)
        while True:
            if pending and not chan.poll(0.0):
                flush()                  # backlog drained: ship the batch
            try:
                msg = chan.get()
            except CorruptFrame:
                # the transport skipped a mangled frame; if it mattered
                # the session layer will heal it — just keep serving
                continue
            if isinstance(msg, StepGrant):        # hot path first
                if executor is None and spec.train:
                    with tr.span("worker", "train_init"):
                        try:
                            executor = TrainExecutor(spec)
                        except Exception as e:
                            # a worker that cannot train must fail the
                            # run, not read as a dropout: say why, exit
                            error = f"{type(e).__name__}: {e}"
                            LOG.warn("worker_init_failed",
                                     f"worker {spec.group}: "
                                     f"{traceback.format_exc()}",
                                     group=spec.group, error=error)
                            chan.put(Goodbye(spec.group, worker_step,
                                             error=error))
                            exit_status = "failed"
                            break
                t0 = tr.now() if tr else 0.0
                report = _one_step(spec, gov, sm, executor, msg.step,
                                   speed_memo)
                worker_step += 1
                if tr:
                    tr.complete("worker", "step", t0, tr.now() - t0,
                                {"step": msg.step,
                                 "batch": spec.batch_size})
                    if report is None:
                        tr.instant("worker", "silenced",
                                   {"step": msg.step})
                    else:
                        cap = gov.capacity(msg.step)
                        if cap < 1.0 or gov.speed_cap(msg.step) is not None:
                            tr.instant("worker", "throttled",
                                       {"step": msg.step, "capacity": cap})
                if report is not None:
                    speed_history.append(report.speed)
                    pending.append(report)
                continue
            if isinstance(msg, Shutdown):
                flush()
                chan.put(Goodbye(spec.group, worker_step))
                exit_status = "shutdown"
                break
            if isinstance(msg, Retune):
                spec.batch_size = int(
                    msg.batch_sizes.get(spec.group, spec.batch_size))
                if tr:
                    tr.instant("worker", "retune_applied",
                               {"step": msg.step,
                                "batch": spec.batch_size,
                                "reason": msg.reason})
                continue
            if isinstance(msg, CheckpointRequest):
                flush()                  # reports precede their ack
                if bulk_plane is None and spec.bulk == "shm" \
                        and shm_available():
                    try:
                        bulk_plane = ShmBulkPlane()
                    except (BulkUnavailable, OSError):
                        spec.bulk = "inline"     # degrade, don't retry
                state = {
                    "group": spec.group,
                    "worker_step": worker_step,
                    "batch_size": spec.batch_size,
                    "n_compiles": executor.n_compiles if executor else 0,
                    "speed_history": list(speed_history),
                }
                if executor is not None:
                    state.update(executor.summary())
                state = json.dumps(state, separators=(",", ":")).encode(
                    "utf-8")
                ack = CheckpointAck(
                    msg.step, spec.group, worker_step, spec.batch_size,
                    executor.n_compiles if executor else 0,
                    state=publish_bulk(state, bulk_plane))
                if tr:
                    # events traced since the last report flush still
                    # ship (the final drain is often ack-only traffic)
                    ack.obs = tr.drain_wire() or None
                chan.put(ack)
                continue
    except ChannelClosed:
        pass                                     # coordinator gone: exit
    finally:
        if bulk_plane is not None:
            bulk_plane.close()
        carry: List[Message] = list(pending)
        if isinstance(chan, ReliableChannel) and exit_status == "closed":
            carry.extend(m for m in chan.unacked_messages()
                         if not isinstance(m, Goodbye))
        chan.close()
    return WorkerExit(exit_status, carry, error)


def _one_step(spec: WorkerSpec, gov: SpeedGovernor, sm: SpeedModel,
              executor: Optional[TrainExecutor], step: int,
              speed_memo: Optional[Dict[float, float]] = None
              ) -> Optional[StepReportMsg]:
    """Execute (maybe) and report (maybe) one granted round.

    Report semantics mirror the simulator exactly (same float ops, same
    order) so a governed runtime stream is bit-identical to a
    ``ClusterSim`` stream and trace parity holds:

      b == 0   -> benchmark knee speed, cpu_util 0 (idle-but-alive);
      b > 0    -> speed(b) × capacity, min absolute cap; cpu_util is the
                  capacity fraction. With a TrainExecutor the raw speed
                  is the real measured b/dt instead of the curve.

    ``speed_memo`` caches the pure curve lookup ``sm.speed(b)`` per
    batch size (the np.interp call was a measurable slice of the
    report-only step on the protocol hot path). The quiet-worker exit —
    no interference windows, no silence — short-circuits the window
    evaluation with the literal values the helpers return for an empty
    schedule (capacity 1.0, no cap), so the emitted floats are
    bit-identical to the slow path."""
    loss = wall_dt = None
    if executor is not None and spec.batch_size > 0:
        loss, wall_dt = executor.run_step(spec.batch_size)
    elif spec.step_delay_s > 0.0:
        time.sleep(spec.step_delay_s)    # modeled compute (GIL released)
    if speed_memo is None:
        speed_memo = {}
    quiet = not gov.windows and not gov.silence
    if not quiet and gov.silenced(step):
        return None
    if spec.batch_size == 0:
        knee = sm.knee()
        if knee not in speed_memo:
            speed_memo[knee] = sm.speed(knee)
        return StepReportMsg(step, spec.group, speed_memo[knee],
                             cpu_util=0.0, batch_size=0)
    if wall_dt is not None:
        raw = spec.batch_size / wall_dt
    else:
        raw = speed_memo.get(spec.batch_size)
        if raw is None:
            raw = speed_memo[spec.batch_size] = \
                sm.speed(spec.batch_size)
    if quiet:
        return StepReportMsg(step, spec.group, raw * 1.0,
                             cpu_util=1.0, batch_size=spec.batch_size,
                             wall_dt=wall_dt, loss=loss)
    return StepReportMsg(step, spec.group, gov.govern(raw, step),
                         cpu_util=gov.capacity(step),
                         batch_size=spec.batch_size,
                         wall_dt=wall_dt, loss=loss)


def worker_entry(spec_wire: Dict, connection) -> None:
    """Spawn-context process entry point: rebuild the spec from wire
    primitives and wrap the inherited Connection."""
    from repro.runtime.ipc.pipe import PipeChannel

    done = run_worker(WorkerSpec.from_wire(spec_wire),
                      PipeChannel(connection))
    if done.status == "failed":
        raise SystemExit(f"worker: {done.error}")
