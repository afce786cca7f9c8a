"""The Stannis coordinator: an event loop owning the control plane.

Per coordinator round the loop

  1. applies any scheduled fault-injection actions (kill / restart /
     suspend / resume, delegated to the execution manager);
  2. paces every live worker with ``StepGrant``s, keeping up to
     ``staleness`` (k) rounds of grants in flight beyond the round it is
     collecting — the coordinator owns the logical clock, workers stamp
     reports with the granted step;
  3. assembles the round's reports, accepting out-of-order arrivals
     into per-step buckets (:class:`~repro.core.control.telemetry.
     StepBuckets`) and waiting — bounded by ``round_timeout`` — until
     the round's bucket is complete-enough (every worker granted that
     step and still on the same incarnation has answered). A killed
     worker surfaces as channel EOF, a suspended worker as a timeout —
     EITHER WAY the bus simply receives nothing and the existing
     ControlPlane liveness path masks the group out after
     ``liveness_timeout`` silent *coordinator rounds* (never granted
     steps: a run-ahead worker's pre-delivered reports only defer
     detection by at most k rounds, they cannot suppress it);
  4. publishes the round's reports on the ``TelemetryBus`` and runs one
     control round (rejoin -> policies -> liveness);
  5. broadcasts any plan change as a ``Retune`` message — workers flip
     their row mask, nothing recompiles — and measures propagation lag
     from the worker-echoed batch size, one pending entry per
     (group, decision step).

The loop is transport-blind: a worker behind a thread pipe, a spawned
process pipe, or a TCP socket on another host (DESIGN.md §12) receives
the same StepGrants, Retune row-mask broadcasts and bounded-staleness
pacing — host identity from the Hello handshake is carried through to
``RuntimeResult.hosts`` (the cluster map), but never consulted by the
control flow. That invariance is what the per-transport parity tests
pin down.

With ``staleness=0`` pacing is the strict rendezvous (grant -> report)
of PR 2: a fully-live cluster runs with zero timeouts and the round
sequence is deterministic — the same scenario replayed through
:class:`~repro.core.simulator.ClusterSim` and through this loop produces
the identical event stream (tests/test_runtime*.py assert the paper's
180 -> 140 -> 100 Fig. 6 sequence through both). With ``staleness=k>0``
the grant pipeline keeps workers busy while the coordinator processes
older rounds; a ``Retune`` decided at round r is queued behind the
grants already in flight, so it takes effect on the worker at step
r+k+1 — deterministically, which is what lets ``ClusterSim(staleness=k)``
mirror the mode for trace parity at any k.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

from repro.core.allocator import BatchPlan
from repro.core.control import ControlPlane, RetuneEvent, StepBuckets, \
    StepReport
from repro.obs import LOG, NULL_TRACER
from repro.runtime.ipc import (ChannelClosed, CorruptFrame, ReliableChannel,
                               find_chaos, wait_readable)
from repro.runtime.ipc.shm import (BulkUnavailable, ShmBulkReader,
                                   inline_ref, resolve_bulk)
from repro.runtime.managers.base import ExecutionManager
from repro.runtime.messages import (CheckpointAck, CheckpointRequest, Goodbye,
                                    Hello, Message, ReportBatch, Retune,
                                    Shutdown, StepGrant, StepReportMsg)
from repro.runtime.worker import InterferenceSpec, WorkerSpec


class WorkerFailed(RuntimeError):
    """A worker could not serve at all (e.g. its training executor
    failed to build). Unlike a dropout, this ends the run."""


@dataclasses.dataclass
class FaultAction:
    """One scheduled fault-injection action. ``action`` is one of
    "kill" | "restart" | "suspend" | "resume" | "partition" | "heal".

    "partition"/"heal" drive the chaos plane's partition scheduler
    (DESIGN.md §15): the coordinator<->group link is severed/restored
    at an exact round boundary — which is what lets ``ClusterSim``
    mirror a partition window as a ``Dropout`` of the same steps."""

    step: int
    action: str
    group: str


@dataclasses.dataclass
class RoundStats:
    step: int
    n_reports: int
    latency_s: float
    event: Optional[str] = None


@dataclasses.dataclass
class RuntimeResult:
    rounds: int
    events: List[RetuneEvent]
    round_stats: List[RoundStats]
    wall_time: float
    reports_total: int
    retune_lags: List[int]               # rounds from decision to worker echo
    checkpoint_acks: List[CheckpointAck]
    staleness: int = 0
    stale_reports: int = 0               # below-floor arrivals discarded
    acks_dropped: int = 0                # checkpoint acks expired on timeout
    # group -> worker location ("host@endpoint") from the Hello
    # handshake: the cluster map on a multi-host (socket) mesh
    hosts: Dict[str, str] = dataclasses.field(default_factory=dict)
    # the run's MetricsRegistry when one was attached (DESIGN.md §14):
    # benches and examples read round/lag stats from HERE instead of
    # re-deriving them from round_stats ad hoc
    metrics: Optional[object] = None

    def event_tuples(self):
        return [(e.step, e.group, e.old_batch, e.new_batch, e.reason)
                for e in self.events]

    @property
    def reports_per_s(self) -> float:
        return self.reports_total / max(self.wall_time, 1e-9)

    @property
    def mean_round_latency_s(self) -> float:
        if not self.round_stats:
            return 0.0
        return sum(r.latency_s for r in self.round_stats) / \
            len(self.round_stats)


class RetuneLagTracker:
    """Propagation-lag bookkeeping, one pending entry per
    (group, decision step).

    Keying by group alone (PR 2) meant a second retune for the same
    group overwrote the first entry before its echo arrived — the first
    lag was never recorded, and a late echo of the OLD batch size could
    match the new entry. Here every decision keeps its own slot; an
    echo matches the oldest pending entry carrying that batch size, and
    matching an entry expires every older entry for the group (the
    worker is provably past them — their echo can never arrive).

    ``min_lag`` is the earliest a genuine echo can possibly arrive:
    channels are FIFO and the coordinator has already sent grants
    through round s+k when it broadcasts a retune decided at round s,
    so no report stamped <= s+k can reflect it — a genuine echo has
    lag >= k+1. Requiring that rejects the flapping false-positive
    where a second retune returns to the batch size the worker is
    STILL running (pre-first-retune run-ahead reports would otherwise
    "echo" it with an impossibly small lag, and expire the first
    entry before its real echo arrived)."""

    def __init__(self, min_lag: int = 1) -> None:
        # (group, decision step) -> new batch; insertion-ordered, and
        # decisions arrive in step order, so iteration is oldest-first
        self._pending: Dict[Tuple[str, int], int] = {}
        self.min_lag = min_lag

    def note(self, step: int, group: str, new_batch: int) -> None:
        self._pending[(group, step)] = new_batch

    def match(self, round_: int, group: str,
              batch_size: int) -> Optional[int]:
        """An echoed batch size observed at coordinator ``round_``.
        Returns the measured lag in rounds, or None if it answers no
        pending entry."""
        hit = next((s for (g, s), bs in self._pending.items()
                    if g == group and bs == batch_size
                    and round_ - s >= self.min_lag), None)
        if hit is None:
            return None
        for key in [k for k in self._pending
                    if k[0] == group and k[1] <= hit]:
            del self._pending[key]           # matched + superseded ones
        return round_ - hit

    def pending(self) -> Dict[Tuple[str, int], int]:
        return dict(self._pending)


def specs_from_plan(plan: BatchPlan,
                    interferences: Sequence = (),
                    dropouts: Sequence = (),
                    train: Optional[Dict] = None,
                    seed: int = 0,
                    step_delay_s: float = 0.0,
                    obs: bool = False) -> List[WorkerSpec]:
    """One WorkerSpec per plan group, carrying its benchmark table and
    its slice of the fault schedule. ``interferences``/``dropouts`` are
    the simulator's dataclasses — the runtime and ``ClusterSim`` consume
    the SAME scenario description (trace parity by construction).
    ``obs`` turns on worker-side tracing (DESIGN.md §14)."""
    specs = []
    for g in plan.groups:
        ivs = [InterferenceSpec(iv.start_step, iv.end_step, iv.capacity,
                                iv.speed_cap)
               for iv in interferences if iv.group == g.name]
        sil = [(d.start_step, d.end_step)
               for d in dropouts if d.group == g.name]
        specs.append(WorkerSpec(
            group=g.name, batch_size=g.batch_size, capacity=g.capacity,
            count=g.count,
            speed_batches=[float(b) for b in g.speed_model.batch_sizes],
            speed_speeds=[float(s) for s in g.speed_model.speeds],
            interference=ivs, silence=sil,
            train=dict(train) if train else None, seed=seed,
            step_delay_s=step_delay_s, obs=obs))
    return specs


class EventLoop:
    def __init__(self, control_plane: ControlPlane,
                 manager: ExecutionManager,
                 round_timeout: float = 1.0,
                 staleness: int = 0,
                 ack_timeout: Optional[float] = None,
                 tracer=None,
                 metrics=None,
                 metrics_every: int = 0,
                 round_hook=None) -> None:
        if staleness < 0:
            raise ValueError(f"staleness must be >= 0, got {staleness}")
        self.control_plane = control_plane
        self.manager = manager
        self.round_timeout = round_timeout
        self.staleness = int(staleness)
        # search-layer hook (DESIGN.md §17): called once per round after
        # the control round with the step number; returns the
        # RetuneEvents it applied through the control plane. An event
        # with reason "pruned" retires the group (orderly Shutdown, no
        # new message kinds); anything else broadcasts as a normal
        # Retune and is lag-tracked like a policy decision.
        self.round_hook = round_hook
        self._retired: set = set()
        # observability plane (DESIGN.md §14). NULL_TRACER is falsy, so
        # every `if self.tracer:` below is a dead branch when disabled —
        # the untraced hot path allocates and times NOTHING extra, which
        # is what keeps the Fig. 6 parity gates identical traced/untraced.
        self.tracer = tracer if tracer is not None else NULL_TRACER
        self.metrics = metrics
        self.metrics_every = int(metrics_every)
        self._obs = bool(self.tracer) or metrics is not None
        # (group, step) -> grant send time, for grant->report latency
        self._grant_ts: Dict[Tuple[str, int], float] = {}
        if self.tracer:
            # hand the coordinator tracer to the control plane and the
            # bus so retune decisions / subscriber errors land in the
            # same timeline
            control_plane.tracer = self.tracer
            control_plane.bus.tracer = self.tracer
        # checkpoint acks outlive their round; give them a longer leash
        self.ack_timeout = (ack_timeout if ack_timeout is not None
                            else 4.0 * round_timeout)
        self._ckpt_acks: List[CheckpointAck] = []
        # per-checkpoint-step outstanding acks: {ckpt step: {group: inc}}
        self._awaiting_acks: Dict[int, Dict[str, int]] = {}
        self._ack_deadlines: Dict[int, float] = {}
        self._acks_dropped = 0
        self._lag = RetuneLagTracker(min_lag=self.staleness + 1)
        self._lags: List[int] = []
        self._buckets = StepBuckets()
        if metrics is not None:
            # live depth of the out-of-order assembly: how many rounds
            # sit partially collected at once (≈ staleness window)
            self._buckets.on_depth = metrics.gauge("coord.bucket_depth").set
        # per step: {group: incarnation granted} — a report is only owed
        # by the worker life the grant was actually delivered to
        self._expected: Dict[int, Dict[str, int]] = {}
        self._granted_hi: Dict[str, int] = {}    # group -> highest granted
        self._stale_reports = 0
        # lazy shm attach: only built when a CheckpointAck actually
        # carries a shm bulk reference (same-host workers, DESIGN.md §13)
        self._bulk: Optional[ShmBulkReader] = None

    # ------------------------------------------------------------------
    def run(self, rounds: int, faults: Sequence[FaultAction] = (),
            checkpoint_every: int = 0,
            on_retune=None,
            journal=None, journal_every: int = 0,
            start: int = 0) -> RuntimeResult:
        """Run rounds ``start..rounds-1``. ``journal`` (a
        :class:`~repro.checkpoint.checkpointer.RunJournal`) with
        ``journal_every`` > 0 persists the coordinator's resumable
        state every N completed rounds; ``start`` > 0 is the resume
        path — call :meth:`restore` with the journaled state first,
        then pass its ``next_round`` here (DESIGN.md §15)."""
        cp = self.control_plane
        stats: List[RoundStats] = []
        reports_total = 0
        obs = self._obs
        tr = self.tracer
        mx = self.metrics
        t_run = time.perf_counter()
        for step in range(start, rounds):
            t0 = time.perf_counter()
            self._apply_faults(step, faults)
            self._admit_rejoins()
            self._grant_ahead(step, rounds)
            tg = time.perf_counter() if obs else t0
            reports = self._collect_round(step)
            tc = time.perf_counter() if obs else t0
            reports_total += len(reports)
            for msg in reports.values():
                cp.bus.publish(StepReport(step, msg.group, msg.speed,
                                          cpu_util=msg.cpu_util,
                                          power_w=msg.power_w))
                lag = self._lag.match(step, msg.group, msg.batch_size)
                if lag is not None:
                    self._lags.append(lag)
                    if obs:
                        # decision->effect: the worker's echoed batch
                        # size proves the retune landed, `lag` rounds on
                        if tr:
                            tr.instant("control", "retune_effect",
                                       {"group": msg.group, "step": step,
                                        "lag_rounds": lag})
                        if mx is not None:
                            mx.histogram(
                                "coord.retune_effect_lag_rounds"
                            ).record(lag)
            event = cp.poll(step)
            td = time.perf_counter() if obs else t0
            if event is not None:
                self._broadcast_retune(step, event)
                if on_retune:
                    on_retune(event)
            if self.round_hook is not None:
                for hev in self.round_hook(step) or ():
                    if hev.reason == "pruned":
                        # the trial is finished, not failing: retire its
                        # worker instead of broadcasting a plan it will
                        # never act on
                        self.retire(step, hev.group)
                    else:
                        self._broadcast_retune(step, hev)
                    if on_retune:
                        on_retune(hev)
            if checkpoint_every and (step + 1) % checkpoint_every == 0:
                self._broadcast(CheckpointRequest(step))
                live = self.manager.live()
                if live:
                    self._awaiting_acks[step] = {
                        n: h.incarnation for n, h in live.items()}
                    self._ack_deadlines[step] = \
                        time.perf_counter() + self.ack_timeout
            self._expire_acks()
            t_end = time.perf_counter()
            if obs:
                if tr:
                    tr.complete("round", "grant", t0, tg - t0)
                    tr.complete("round", "collect", tg, tc - tg,
                                {"reports": len(reports)})
                    tr.complete("round", "decide", tc, td - tc)
                    tr.complete("round", "broadcast", td, t_end - td)
                    tr.complete("round", "round", t0, t_end - t0,
                                {"step": step, "reports": len(reports)})
                if mx is not None:
                    mx.histogram("coord.round_latency_s").record(t_end - t0)
                    mx.counter("coord.reports").inc(len(reports))
                    if event is not None:
                        mx.counter("coord.retunes").inc()
                    if self.metrics_every and \
                            (step + 1) % self.metrics_every == 0:
                        LOG.info("metrics", mx.summary_line(
                            prefix=f"[metrics] round {step}: "))
            stats.append(RoundStats(
                step, len(reports), t_end - t0,
                None if event is None else
                f"{event.group}:{event.old_batch}->{event.new_batch}"
                f" ({event.reason})"))
            if journal is not None and journal_every and \
                    (step + 1) % journal_every == 0:
                journal.save(step + 1, self._journal_state(step + 1))
                if tr:
                    tr.instant("journal", "saved", {"next_round": step + 1})
                if mx is not None:
                    mx.counter("coord.journal_saves").inc()
        self._drain_acks()
        if mx is not None:
            self._scrape_wire_stats()
        return RuntimeResult(rounds, list(cp.events), stats,
                             time.perf_counter() - t_run, reports_total,
                             list(self._lags), list(self._ckpt_acks),
                             staleness=self.staleness,
                             stale_reports=self._stale_reports,
                             acks_dropped=self._acks_dropped,
                             hosts=self.manager.hosts(),
                             metrics=mx)

    def shutdown(self) -> None:
        try:
            self.manager.shutdown()
        finally:
            if self._bulk is not None:
                self._bulk.close()
                self._bulk = None

    # ------------------------------------------------------------------
    def _apply_faults(self, step: int, faults: Sequence[FaultAction]) -> None:
        for f in faults:
            if f.step != step:
                continue
            if self.tracer:
                self.tracer.instant("fault", f.action,
                                    {"group": f.group, "step": step})
            if self.metrics is not None:
                self.metrics.counter(f"coord.faults.{f.action}").inc()
            if f.action == "kill":
                self.manager.kill(f.group)
            elif f.action == "suspend":
                self.manager.suspend(f.group)
            elif f.action == "resume":
                self.manager.resume(f.group)
            elif f.action == "partition":
                self.manager.partition(f.group)
                # sim-parity (DESIGN.md §15): a Dropout of [s, e) means
                # NO reports for steps >= s count — under run-ahead the
                # group may already have delivered reports for steps in
                # the window before the link was severed; discard them
                # so a partition is step-exact, not arrival-time-racy
                purged = self._buckets.discard_group(f.group, step)
                if purged and self.tracer:
                    self.tracer.instant("fault", "partition_purge",
                                        {"group": f.group, "step": step,
                                         "purged": purged})
            elif f.action == "heal":
                self.manager.heal(f.group)
            elif f.action == "restart":
                handle = self.manager.workers.get(f.group)
                if handle is None:
                    known = ", ".join(sorted(self.manager.workers)) \
                        or "<none>"
                    raise ValueError(
                        f"cannot restart unknown group {f.group!r}: it was "
                        f"never started by this manager (known groups: "
                        f"{known})")
                spec = dataclasses.replace(
                    handle.spec,
                    batch_size=self.control_plane.plan.batch_sizes().get(
                        f.group, handle.spec.batch_size))
                self.manager.restart(f.group, spec)
                # the new incarnation starts its grant stream at the
                # current round — its predecessor's grants died with it
                self._granted_hi.pop(f.group, None)
            else:
                raise ValueError(f"unknown fault action: {f.action}")

    # -- group retirement (search layer, DESIGN.md §17) -----------------
    def retire(self, step: int, group: str) -> int:
        """Permanently retire one worker group mid-run (a pruned trial).

        Rides existing message kinds only: the worker gets an orderly
        ``Shutdown`` and its channel is closed. Retirement is step-exact
        under run-ahead, mirroring the simulator's ``retired`` set: the
        group's reports for steps > ``step`` — already bucketed by a
        run-ahead worker — are discarded via ``StepBuckets.
        discard_group``, its pending grant expectations are dropped (so
        collection never waits on a worker that is gone), and a
        self-healing reconnect of a retired group is refused. Returns
        the number of buffered reports discarded."""
        self._retired.add(group)
        purged = self._buckets.discard_group(group, step + 1)
        for s in list(self._expected):
            if s > step:
                self._expected[s].pop(group, None)
        self._granted_hi.pop(group, None)
        handle = self.manager.workers.get(group)
        if handle is not None and handle.alive:
            try:
                handle.channel.put(Shutdown())
            except ChannelClosed:
                pass
            self.manager.mark_dead(group)
        if self.tracer:
            self.tracer.instant("control", "retire",
                                {"group": group, "step": step,
                                 "purged": purged})
        if self.metrics is not None:
            self.metrics.counter("coord.search.retired").inc()
            if purged:
                self.metrics.counter(
                    "coord.search.purged_reports").inc(purged)
        return purged

    def _admit_rejoins(self) -> None:
        """Pump the manager's mid-run rejoin path (self-healing socket
        workers, DESIGN.md §15). A no-op — one virtual call returning
        an empty list — for in-process managers."""
        rejoined = self.manager.admit_rejoins(
            self.control_plane.plan.batch_sizes())
        for g in rejoined:
            if g in self._retired:
                # a retired (pruned) trial's standalone worker trying to
                # self-heal its way back in: refuse — the trial is over
                self.manager.mark_dead(g)
                continue
            # the new life's grant stream starts at the current round;
            # grants delivered to its predecessor died with the old TCP
            # session (their unacked replay died with the old wrapper)
            self._granted_hi.pop(g, None)
            if self.tracer:
                self.tracer.instant("fault", "worker_rejoin", {"group": g})
            if self.metrics is not None:
                self.metrics.counter("coord.faults.rejoin").inc()

    # -- crash-resume (DESIGN.md §15) -----------------------------------
    def _journal_state(self, next_round: int) -> Dict:
        """Everything a restarted coordinator needs to continue this
        run from round ``next_round``, as JSON primitives."""
        return {
            "next_round": next_round,
            "staleness": self.staleness,
            "control": self.control_plane.snapshot(),
            "bucket_floor": self._buckets.floor,
            "lags": list(self._lags),
            "lag_pending": [[g, s, bs] for (g, s), bs in
                            self._lag.pending().items()],
            "stale_reports": self._stale_reports,
            "acks_dropped": self._acks_dropped,
            "awaiting_acks": {str(s): dict(pend) for s, pend in
                              self._awaiting_acks.items()},
        }

    def restore(self, state: Dict) -> int:
        """Rehydrate from a journal entry (before :meth:`run` with
        ``start=<returned round>``). The control plane replays its
        snapshot onto the freshly-built plan; grant/bucket bookkeeping
        fast-forwards so re-delivered frames from before the crash are
        recognized as stale. Outstanding checkpoint acks are restored
        as owed-by-dead-lives: the dead coordinator's workers died with
        it, so the first ``_expire_acks`` counts them dropped — which
        is the truth."""
        if int(state.get("staleness", self.staleness)) != self.staleness:
            raise ValueError(
                f"journal was written at staleness "
                f"{state.get('staleness')}, this loop runs "
                f"{self.staleness}: the run cannot continue "
                f"deterministically")
        self.control_plane.restore_snapshot(state["control"])
        self._buckets.restore_floor(int(state.get("bucket_floor", 0)))
        self._lags = [int(v) for v in state.get("lags", [])]
        for g, s, bs in sorted(state.get("lag_pending", []),
                               key=lambda e: e[1]):
            self._lag.note(int(s), str(g), int(bs))
        self._stale_reports = int(state.get("stale_reports", 0))
        self._acks_dropped = int(state.get("acks_dropped", 0))
        now = time.perf_counter()
        for s, pend in state.get("awaiting_acks", {}).items():
            self._awaiting_acks[int(s)] = {str(g): int(i)
                                           for g, i in pend.items()}
            self._ack_deadlines[int(s)] = now + self.ack_timeout
        return int(state["next_round"])

    # -- grant pipeline -------------------------------------------------
    def _grant_ahead(self, step: int, rounds: int) -> None:
        """Keep every live worker granted through ``step + staleness``
        (capped at the final round). At staleness=0 this issues exactly
        one grant per worker per round — the synchronous rendezvous."""
        hi = min(step + self.staleness, rounds - 1)
        for name, handle in self.manager.live().items():
            lo = max(self._granted_hi.get(name, step - 1) + 1, step)
            for s in range(lo, hi + 1):
                try:
                    handle.channel.put(StepGrant(s, self.staleness))
                except ChannelClosed:
                    self._note_eof(name)
                    break
                self._granted_hi[name] = s
                self._expected.setdefault(s, {})[name] = handle.incarnation
                if self._obs:
                    self._grant_ts[(name, s)] = time.perf_counter()

    # -- collection -----------------------------------------------------
    def _collect_round(self, step: int) -> Dict[str, StepReportMsg]:
        """Assemble round ``step``'s bucket: one report per worker that
        was granted the step and is still on that incarnation, or
        silence by the deadline. Out-of-order arrivals for later rounds
        are bucketed for their own round; below-floor arrivals (e.g. a
        resumed worker's backlog flush) are discarded as stale."""
        deadline = time.perf_counter() + self.round_timeout
        while True:
            # bucket already complete (a run-ahead worker's batch landed
            # during an earlier round's drain): zero syscalls this round
            if not self._missing(step):
                break
            progressed = self._pump(step)
            missing = self._missing(step)
            if not missing:
                break
            now = time.perf_counter()
            if now >= deadline:
                break
            if not progressed:
                # event-driven wait over EVERY owing worker at once: one
                # select() wakes the instant any of them produces data
                # (or EOFs). The old form blocked on missing[0] alone,
                # serializing the wait on one worker while others sat
                # readable — measurable at staleness > 0, where rounds
                # complete out of order.
                wait_readable(
                    [self.manager.workers[n].channel for n in missing],
                    deadline - now)
        self._expected.pop(step, None)
        return self._buckets.pop(step)

    def _missing(self, step: int) -> List[str]:
        """Workers still owing round ``step`` a report: granted it, not
        yet bucketed, alive, and on the incarnation the grant went to."""
        got = self._buckets.peek(step)
        out = []
        for name, inc in self._expected.get(step, {}).items():
            if name in got:
                continue
            handle = self.manager.workers.get(name)
            if handle is None or not handle.alive or \
                    handle.incarnation != inc:
                continue                 # that worker life is gone
            out.append(name)
        return out

    def _pump(self, floor: Optional[int]) -> bool:
        """Drain every live worker's channel, routing messages. Returns
        True when anything arrived.

        The readiness sweep is ONE ``wait_readable(..., 0.0)`` (a single
        select over every worker fd) rather than a per-channel
        ``poll(0.0)`` — on the syscall-bound coordinator hot path the
        N-per-sweep selects were measurable. Only ready channels are
        then drained, in name order for determinism."""
        progressed = False
        live = sorted(self.manager.live())
        ready = wait_readable(
            [self.manager.workers[n].channel for n in live], 0.0)
        ready_ids = {id(c) for c in ready}
        for name in live:
            handle = self.manager.workers[name]
            chan = handle.channel
            if id(chan) not in ready_ids:
                continue
            try:
                while chan.poll(0.0):
                    self._route(name, self._get(chan, name), floor)
                    progressed = True
                    # frames already reassembled in-process (several per
                    # recv under coalescing) drain without re-selecting
                    while chan.has_buffered():
                        self._route(name, self._get(chan, name), floor)
            except ChannelClosed:
                self._note_eof(name)
                progressed = True
        return progressed

    def _get(self, chan, name: str) -> Optional[Message]:
        """One receive, tolerating the bounded-resync path: a corrupt
        frame is counted loudly and skipped — the session layer (or
        plain retransmission) heals whatever it carried. Returns None
        for the skipped frame (``_route`` ignores None)."""
        try:
            return chan.get()
        except CorruptFrame:
            if self.tracer:
                self.tracer.instant("fault", "corrupt_frame",
                                    {"group": name})
            if self.metrics is not None:
                self.metrics.counter("coord.faults.corrupt_frame").inc()
            return None

    def _note_eof(self, name: str) -> None:
        """A worker's channel hit EOF: it died (or was killed). Derived
        liveness handles the consequences; here we just mark and trace."""
        self.manager.mark_dead(name)
        if self.tracer:
            self.tracer.instant("fault", "worker_eof", {"group": name})
        if self.metrics is not None:
            self.metrics.counter("coord.faults.eof").inc()

    def _route(self, name: str, msg: Optional[Message],
               floor: Optional[int]) -> None:
        """Dispatch one arrival. ``floor`` is the oldest round still
        being assembled; report arrivals below it are stale (the
        synchronous loop's ``msg.step != step`` filter, generalized).
        ``floor=None`` (the final ack drain) drops reports silently.
        ``msg=None`` is a corrupt frame ``_get`` already accounted."""
        if msg is None:
            return
        if name in self._retired and not isinstance(msg, Goodbye):
            return                       # in-flight frames of a pruned trial
        if isinstance(msg, StepReportMsg):
            if floor is None:
                return
            if self._obs:
                now = time.perf_counter()
                self._note_grant_latency(name, msg.step, now)
                self._ingest_obs(name, msg.obs, now)
            if not self._buckets.add(msg.step, name, msg):
                self._stale_reports += 1
                if self.metrics is not None:
                    self.metrics.counter("coord.stale_reports").inc()
        elif isinstance(msg, ReportBatch):
            # a coalesced run-ahead window: bucket report by report, in
            # order — semantics identical to k single frames
            if floor is None:
                return
            reps = msg.unpack()
            if self._obs:
                now = time.perf_counter()
                for rep in reps:
                    self._note_grant_latency(name, rep.step, now)
                self._ingest_obs(name, msg.obs, now)
                if self.metrics is not None:
                    self.metrics.histogram(
                        "coord.report_batch_size").record(len(reps))
            for rep in reps:
                if not self._buckets.add(rep.step, name, rep):
                    self._stale_reports += 1
                    if self.metrics is not None:
                        self.metrics.counter("coord.stale_reports").inc()
        elif isinstance(msg, CheckpointAck):
            if self._obs:
                self._ingest_obs(name, msg.obs, time.perf_counter())
                if self.metrics is not None and msg.state is not None \
                        and msg.state:
                    self.metrics.counter(
                        "coord.shm.bulk_hits" if msg.state[0] == "shm"
                        else "coord.shm.inline").inc()
            if msg.state is not None and msg.state and msg.state[0] == "shm":
                # normalize the shm reference to inline bytes NOW, while
                # the worker's ring still holds the chunk; consumers of
                # RuntimeResult.checkpoint_acks only ever see the inline
                # form (or None when the segment is already gone)
                if self._bulk is None:
                    self._bulk = ShmBulkReader()
                try:
                    msg.state = inline_ref(resolve_bulk(msg.state,
                                                        self._bulk))
                except BulkUnavailable:
                    msg.state = None
                    if self.metrics is not None:
                        self.metrics.counter(
                            "coord.shm.bulk_unavailable").inc()
            self._ckpt_acks.append(msg)
            pend = self._awaiting_acks.get(msg.step)
            if pend is not None:
                pend.pop(name, None)
                if not pend:
                    self._awaiting_acks.pop(msg.step, None)
                    self._ack_deadlines.pop(msg.step, None)
        elif isinstance(msg, Goodbye):
            self.manager.mark_dead(name)
            if msg.error:
                raise WorkerFailed(f"worker {name!r} failed: {msg.error}")
        elif isinstance(msg, Hello):
            pass                         # late duplicate; handshake owns it

    # -- observability helpers (DESIGN.md §14) --------------------------
    def _note_grant_latency(self, name: str, step: int, now: float) -> None:
        """grant->report latency per worker: time from the grant leaving
        the coordinator to its report arriving back."""
        t = self._grant_ts.pop((name, step), None)
        if t is not None and self.metrics is not None:
            self.metrics.histogram(
                f"coord.grant_report_latency_s.{name}").record(now - t)

    def _ingest_obs(self, name: str, obs_events, now: float) -> None:
        """Merge a worker's piggybacked trace-event batch into the
        coordinator timeline, keyed ``group#incarnation`` so a restarted
        worker gets its own clock epoch."""
        if not obs_events or not self.tracer:
            return
        handle = self.manager.workers.get(name)
        inc = handle.incarnation if handle is not None else 0
        self.tracer.ingest(f"{name}#{inc}", obs_events, now)

    def _scrape_wire_stats(self) -> None:
        """Fold per-channel frame/byte counters (transports that keep
        them, e.g. the socket plane) into the registry, keyed by the
        channel's negotiated codec — plus, on chaos-hardened links, the
        injector's fault counters and the session layer's healing stats
        (retransmits, recovery-time histogram)."""
        mx = self.metrics
        if mx is None:
            return
        for handle in self.manager.workers.values():
            stats_fn = getattr(handle.channel, "wire_stats", None)
            ws = stats_fn() if stats_fn is not None else None
            if ws:                       # wrappers return None over
                codec = ws.get("codec", "json")  # stat-less transports
                for key in ("frames_out", "bytes_out", "frames_in",
                            "bytes_in", "corrupt_frames"):
                    n = int(ws.get(key, 0))
                    if n:
                        mx.counter(f"wire.{key}.{codec}").inc(n)
            cc = find_chaos(handle.channel)
            if cc is not None:
                for key, n in cc.chaos_stats().items():
                    if n:
                        mx.counter(f"chaos.{key}").inc(int(n))
            if isinstance(handle.channel, ReliableChannel):
                ss = handle.channel.session_stats()
                for key in ("sent", "retransmits", "fast_retransmits",
                            "dup_delivered", "gaps", "corrupt_skipped",
                            "acks_sent", "recovered"):
                    n = int(ss.get(key, 0))
                    if n:
                        mx.counter(f"session.{key}").inc(n)
                hist = mx.histogram("session.recovery_s")
                for d in handle.channel.recovery_s:
                    hist.record(d)

    # -- checkpoint acks ------------------------------------------------
    def _expire_acks(self,
                     deadline_override: Optional[float] = None) -> None:
        """Per-checkpoint-step bookkeeping: a still-outstanding ack set
        is only dropped on ITS OWN explicit timeout (or when the owing
        worker life is gone) — a later CheckpointRequest broadcast never
        clobbers it (the PR-2 overwrite bug, when ``checkpoint_every``
        was small relative to ``round_timeout``). The final drain caps
        every per-step deadline at ``deadline_override``."""
        now = time.perf_counter()
        for ckpt_step in list(self._awaiting_acks):
            pend = self._awaiting_acks[ckpt_step]
            for name in [n for n, inc in pend.items()
                         if (self.manager.workers.get(n) is None
                             or not self.manager.workers[n].alive
                             or self.manager.workers[n].incarnation != inc)]:
                pend.pop(name)           # dead/restarted: can never ack
            deadline = self._ack_deadlines.get(ckpt_step, 0.0)
            if deadline_override is not None:
                deadline = min(deadline, deadline_override)
            if pend and now < deadline:
                continue
            self._acks_dropped += len(pend)
            self._awaiting_acks.pop(ckpt_step, None)
            self._ack_deadlines.pop(ckpt_step, None)

    def _drain_acks(self) -> None:
        """A CheckpointRequest broadcast on the FINAL round would
        otherwise never be answered in a collection pass — drain the
        outstanding acks so the result reflects the workers' final
        state."""
        deadline = time.perf_counter() + self.round_timeout
        while self._awaiting_acks and time.perf_counter() < deadline:
            if not self._pump(None):
                time.sleep(0.002)
            self._expire_acks(deadline_override=deadline)

    # -- broadcast ------------------------------------------------------
    def _broadcast_retune(self, step: int, event: RetuneEvent) -> None:
        self._broadcast(Retune(step, self.control_plane.plan.batch_sizes(),
                               group=event.group, reason=event.reason))
        self._lag.note(step, event.group, event.new_batch)

    def _broadcast(self, msg: Message) -> None:
        for name, handle in self.manager.live().items():
            try:
                handle.channel.put(msg)
            except ChannelClosed:
                self.manager.mark_dead(name)
