"""End-to-end heterogeneous training driver (deliverable b).

Wires the whole stack together the way a fleet deployment would:

  probe -> allocate (equal step time, Eq. 1) -> pjit train loop
        -> per-step StepReports on the TelemetryBus -> ControlPlane
           (pluggable tuning policies, Eq. 2/3 / cpu-util / energy)
        -> retune = new row mask + Eq. 1 re-split (no recompile)
        -> checkpoint/auto-resume; bus silence -> elastic mask-out.

Four execution substrates, selected with ``--runtime``:

  inproc   the historical single-process loop: real jitted steps, the
           "cluster" simulated at the REPORT level only (interference
           hooks scale the reported per-group speeds exactly as a busy
           node would);
  local    the Stannis runtime (repro.runtime) over thread workers —
           coordinator EventLoop, typed IPC messages, deterministic CI;
  process  the Stannis runtime over REAL worker processes, each running
           the jitted train step at its group's live batch size and
           streaming reports back over a pipe. Faults are real: a killed
           worker produces genuine bus silence. The coordinator never
           touches JAX (its node probe runs in a child that exits
           first), and on a TPU host each training worker owns one chip;
  socket   the multi-host mesh backend: the coordinator listens on
           ``--listen host:port`` and workers join over TCP — spawned
           locally by default, or (with ``--external-workers``)
           standalone ``python -m repro.launch.worker --connect``
           processes on any machine. Same protocol, framed over the
           network; a vanished worker is a socket EOF.

``--interfere`` grammar (comma-separated events):
  csd@20x0.5      capacity 0.5 from step 20, open-ended
  csd@20-40x0.5   capacity 0.5 in steps [20, 40)
  xeon0@5-25v24.3 absolute speed cap 24.3 img/s in [5, 25)
  csd@20-40!      dropout (silent — no reports) in [20, 40)

CLI:
  PYTHONPATH=src python -m repro.launch.train --arch deepseek-7b \
      --steps 50 --groups host:1,csd:4 --interfere csd@20-40x0.5 \
      --runtime process
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import re
import time
from typing import Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import monitoring

from repro.accel import (check_chip_budget, enable_compile_cache,
                         host_tpu_chips, one_chip_env, run_with_env)
from repro.checkpoint.checkpointer import Checkpointer
from repro.configs.base import ArchConfig, get_arch, reduced_config
from repro.core import allocator, hetero_dp
from repro.core.allocator import BatchPlan
from repro.core.control import (ControlPlane, HyperTuneConfig, StepReport,
                                policy_from_config)
from repro.core.speed_model import SpeedModel, probe
from repro.data.pipeline import HeteroPipeline
from repro.kernels import ops
from repro.models.model_factory import aux_inputs, build_model
from repro.obs import (LOG, NULL_TRACER, ChromeTraceSink, EventLog,
                       MetricsRegistry, Tracer, gc_spans)
from repro.optim.optimizer import AdamW, OptConfig


# batch sizes the node probe times (paper §III-A)
PROBE_LADDER = (1, 2, 4, 8)

# the spans every step of HeteroTrainer.run opens, in order
STEP_SPANS = ("input", "h2d", "dispatch", "sync", "report", "control")
_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass
class TrainerConfig:
    seq_len: int = 64
    dataset_size: int = 100_000
    steps: int = 50
    seed: int = 0
    private_frac: float = 0.0
    remat: bool = True
    ckpt_dir: Optional[str] = None
    ckpt_every: int = 0                  # 0 = only explicit saves
    keep_ckpts: int = 3
    log_every: int = 10
    opt: OptConfig = dataclasses.field(default_factory=OptConfig)
    hypertune: HyperTuneConfig = dataclasses.field(
        default_factory=HyperTuneConfig)


@dataclasses.dataclass
class StepRecord:
    step: int
    loss: float
    global_batch: int
    step_time: float
    throughput: float
    retune: Optional[str] = None


class HeteroTrainer:
    """The paper's Stannis loop over a real JAX model."""

    def __init__(self, arch_cfg: ArchConfig, plan: BatchPlan,
                 cfg: Optional[TrainerConfig] = None,
                 state: Optional[Tuple] = None):
        """``state`` is a ``(params, opt_state)`` pair to train from (e.g.
        a probing trainer's), so only one copy of the model state exists;
        None initialises both from ``cfg.seed``.

        ``tracer`` (default ``NULL_TRACER``: off) and ``metrics`` (default
        None: off) may be set at any time; :meth:`run` reads them."""
        self.cfg = cfg or TrainerConfig()
        self.tracer = NULL_TRACER
        self.metrics: Optional[MetricsRegistry] = None
        self.arch_cfg = arch_cfg
        self.plan = plan
        self.model = build_model(arch_cfg)
        # the control plane owns the live plan: policies + elastic
        # liveness (3 silent steps on the bus -> mask-out, reports
        # resuming -> knee-restore), replacing the old controller +
        # HeartbeatMonitor pair. ``controller`` stays as an alias for
        # historical call sites (plan/events surface is identical).
        self.control_plane = ControlPlane(
            plan, [policy_from_config(self.cfg.hypertune)],
            cfg=self.cfg.hypertune, liveness_timeout=3)
        self.controller = self.control_plane
        self.pipeline = HeteroPipeline(
            plan, self.cfg.seq_len, arch_cfg.vocab_size,
            seed=self.cfg.seed, private_frac=self.cfg.private_frac)
        self.opt = AdamW(self.cfg.opt)
        if state is None:
            self.params = self.model.init(jax.random.PRNGKey(self.cfg.seed))
            self.opt_state = self.opt.init(self.params)
        else:
            self.params, self.opt_state = state
        self.step_fn = jax.jit(hetero_dp.make_train_step(
            self.model, self.opt, remat=self.cfg.remat))
        self.ckpt = (Checkpointer(self.cfg.ckpt_dir, keep=self.cfg.keep_ckpts)
                     if self.cfg.ckpt_dir else None)
        self.step = 0
        self.records: List[StepRecord] = []
        self._aux = aux_inputs(arch_cfg, plan.global_capacity,
                               self.cfg.seq_len, jnp.float32, concrete=True)

    # ------------------------------------------------------------------
    @classmethod
    def from_probe(cls, arch_cfg: ArchConfig,
                   groups: Dict[str, Tuple[int, SpeedModel]],
                   cfg: Optional[TrainerConfig] = None,
                   state: Optional[Tuple] = None) -> "HeteroTrainer":
        cfg = cfg or TrainerConfig()
        plan = allocator.solve(groups, cfg.dataset_size)
        return cls(arch_cfg, plan, cfg, state)

    @classmethod
    def for_probe(cls, arch_cfg: ArchConfig,
                  cfg: Optional[TrainerConfig] = None) -> "HeteroTrainer":
        """A trainer that exists to run :meth:`probe_speed_model` before
        the real groups are known (its one-group plan is a placeholder)."""
        boot_plan = allocator.solve(
            {"probe": (1, SpeedModel(np.array([1.0, 2, 4]),
                                     np.array([1.0, 2, 4])))}, 64)
        return cls(arch_cfg, boot_plan, cfg)

    def probe_speed_model(self, batch_ladder=PROBE_LADDER,
                          iters: int = 2) -> SpeedModel:
        """Benchmark THIS node (paper §III-A): time real jitted steps at a
        ladder of batch sizes. On a fleet every node class runs this."""
        model, opt = self.model, self.opt
        step = jax.jit(hetero_dp.make_train_step(model, opt,
                                                 remat=self.cfg.remat))

        def one(bs):
            batch = self._synthetic_batch(bs)
            out = step(self.params, self.opt_state, batch)
            jax.block_until_ready(out[2]["loss"])

        return probe(one, batch_ladder, warmup=1, iters=iters)

    def _synthetic_batch(self, rows: int):
        rng = np.random.default_rng(0)
        toks = rng.integers(0, self.arch_cfg.vocab_size,
                            (rows, self.cfg.seq_len + 1))
        batch = {
            "tokens": jnp.asarray(toks[:, :-1], jnp.int32),
            "targets": jnp.asarray(toks[:, 1:], jnp.int32),
            "sample_mask": jnp.ones((rows,), jnp.float32),
        }
        batch.update(aux_inputs(self.arch_cfg, rows, self.cfg.seq_len,
                                jnp.float32, concrete=True))
        return batch

    # ------------------------------------------------------------------
    # checkpoint / restore
    # ------------------------------------------------------------------
    def save(self) -> None:
        if not self.ckpt:
            return
        extras = {
            "pipeline": self.pipeline.snapshot(),
            "batch_sizes": self.control_plane.plan.batch_sizes(),
            "trainer_step": self.step,
        }
        self.ckpt.save(self.step, {"params": self.params,
                                   "opt": self.opt_state}, extras)

    def resume(self) -> bool:
        """Auto-resume from the newest valid checkpoint. Returns True if
        state was restored."""
        if not self.ckpt:
            return False
        out = self.ckpt.restore_latest({"params": self.params,
                                        "opt": self.opt_state})
        if out is None:
            return False
        step, tree, extras = out
        self.params = jax.tree.map(jnp.asarray, tree["params"])
        self.opt_state = jax.tree.map(jnp.asarray, tree["opt"])
        self.step = int(extras.get("trainer_step", step))
        if "pipeline" in extras:
            self.pipeline.restore(extras["pipeline"])
        if "batch_sizes" in extras:
            # min_batch=0 (retune's own default, made explicit): a group
            # that was masked out (b_g = 0) when the checkpoint was taken
            # must stay failed — regression-locked in test_checkpoint.py
            new = allocator.retune(self.control_plane.plan,
                                   {k: int(v) for k, v in
                                    extras["batch_sizes"].items()},
                                   min_batch=0)
            self.control_plane.plan = new
            self.pipeline.set_plan(new)
        return True

    # ------------------------------------------------------------------
    # the loop
    # ------------------------------------------------------------------
    def run(self, steps: Optional[int] = None,
            report_fn: Optional[Callable[[int, BatchPlan, float],
                                         Dict[str, Dict[str, float]]]] = None,
            on_retune: Optional[Callable] = None) -> List[StepRecord]:
        """report_fn(step, plan, measured_step_time) -> per-group reports.
        Defaults to healthy reports derived from the plan (each group at
        its required speed); tests/examples wrap it to inject interference
        or dropouts (returning no entry for a dead group).

        With ``self.tracer`` set, every step is a run of ``train`` spans
        (``STEP_SPANS``, then ``retune``/``checkpoint``/``log`` when
        they happen), garbage collections are ``train/gc`` spans and
        backend compiles ``train/compile`` instants. With ``self.metrics``
        set, the ``train.*`` counters count rows, retunes and compiles,
        and ``kernels.attention.fused`` / ``.blocked`` the attention calls
        traced while the step is built, by the path that serves each; a
        dropless expert stack adds its step's ``moe.rows.*`` counts
        (``hetero_dp.ROUTING_COUNTERS``)."""
        steps = steps if steps is not None else self.cfg.steps
        target = self.step + steps
        with contextlib.ExitStack() as observing:
            if self.tracer:
                observing.enter_context(gc_spans(self.tracer, "train"))
            if self.tracer or self.metrics is not None:
                listener = self._on_compile
                monitoring.register_event_duration_secs_listener(listener)
                observing.callback(
                    monitoring.unregister_event_duration_listener, listener)
            if self.metrics is not None:
                observing.enter_context(
                    ops.observe_attention(self._on_attention))
            while self.step < target:
                self._step(report_fn, on_retune)
            if self.ckpt:
                with self.tracer.span("train", "checkpoint"):
                    self.save()
                    self.ckpt.wait()
        return self.records

    def _step(self, report_fn, on_retune) -> None:
        tr, mx = self.tracer, self.metrics
        plan = self.control_plane.plan
        with tr.span("train", "input"):
            np_batch = self.pipeline.next_batch()
        with tr.span("train", "h2d"):
            batch = {
                "tokens": jnp.asarray(np_batch["tokens"]),
                "targets": jnp.asarray(np_batch["targets"]),
                "sample_mask": jnp.asarray(np_batch["sample_mask"]),
            }
        batch.update(self._aux)
        t0 = time.perf_counter()
        with tr.span("train", "dispatch"):
            self.params, self.opt_state, out = self.step_fn(
                self.params, self.opt_state, batch)
        with tr.span("train", "sync"):
            loss = float(out["loss"])              # blocks
        dt = max(time.perf_counter() - t0, 1e-9)

        with tr.span("train", "report"):
            reports = (report_fn(self.step, plan, dt) if report_fn
                       else self._healthy_reports(plan))
            for gname, r in reports.items():
                self.control_plane.bus.publish(
                    StepReport.from_legacy(self.step, gname, r))
        with tr.span("train", "control"):
            # one control round: rejoin -> policies -> liveness
            event = self.control_plane.poll(self.step)
        if event is not None:
            with tr.span("train", "retune"):
                self.pipeline.set_plan(self.control_plane.plan)
                if on_retune:
                    on_retune(event)
        if mx is not None:
            mx.counter("train.rows.live").inc(plan.global_batch)
            mx.counter("train.rows.capacity").inc(plan.global_capacity)
            for name in hetero_dp.ROUTING_COUNTERS:
                if name in out:
                    mx.counter(name).inc(int(out[name]))
            if event is not None:
                mx.counter("train.retunes").inc()

        rec = StepRecord(
            self.step, loss, plan.global_batch, dt,
            plan.global_batch / dt,
            retune=None if event is None else
            f"{event.group}:{event.old_batch}->{event.new_batch}")
        self.records.append(rec)
        self.step += 1
        if self.cfg.ckpt_every and self.step % self.cfg.ckpt_every == 0:
            with tr.span("train", "checkpoint"):
                self.save()
        if self.cfg.log_every and self.step % self.cfg.log_every == 0:
            with tr.span("train", "log"):
                LOG.info("train_step",
                         f"step {self.step:5d} loss {loss:.4f} "
                         f"gb {plan.global_batch} "
                         f"({rec.throughput:.1f} samp/s)",
                         step=self.step, loss=loss,
                         global_batch=plan.global_batch,
                         throughput=rec.throughput)

    def _on_compile(self, event: str, seconds: float, **_) -> None:
        """JAX's monitoring hook, registered while :meth:`run` observes."""
        if event != _BACKEND_COMPILE:
            return
        tr, mx = self.tracer, self.metrics
        if tr:
            tr.instant("train", "compile", {"seconds": seconds})
        if mx is not None:
            mx.counter("train.compiles").inc()

    def _on_attention(self, path: str, reason: str) -> None:
        """The attention dispatch's hook, registered while :meth:`run`
        counts."""
        mx = self.metrics
        if mx is not None:
            mx.counter(f"kernels.attention.{path}").inc()

    @staticmethod
    def _healthy_reports(plan: BatchPlan) -> Dict[str, Dict[str, float]]:
        """Every live node reports each step — including idle (b_g = 0)
        ones, which advertise their probe speed so the rejoin path can
        bring them back."""
        out = {}
        for g in plan.groups:
            if g.batch_size == 0:
                out[g.name] = {"speed": g.speed_model.speed(
                    g.speed_model.knee()), "cpu_util": 0.0}
            else:
                out[g.name] = {
                    "speed": g.batch_size / max(plan.step_time, 1e-9),
                    "cpu_util": 1.0,
                }
        return out


# ---------------------------------------------------------------------------
# interference helpers (shared by examples/tests)
# ---------------------------------------------------------------------------


def interference_report_fn(schedule: Dict[str, List[Tuple[int, int, float]]]
                           ) -> Callable:
    """schedule: {group: [(start, end, capacity)]} -> report_fn where an
    interfered group's speed is capacity × its benchmark curve at its
    CURRENT batch (the Gzip stand-in, same model as core/simulator.py) —
    so a correct retune restores the plan step time and the controller
    converges instead of chasing itself down."""

    def fn(step, plan, dt):
        reports = HeteroTrainer._healthy_reports(plan)
        for gname, windows in schedule.items():
            if gname not in reports:
                continue
            g = next(g for g in plan.groups if g.name == gname)
            for s, e, cap in windows:
                if s <= step < e and g.batch_size > 0:
                    sp = cap * g.speed_model.speed(g.batch_size)
                    reports[gname]["speed"] = min(reports[gname]["speed"],
                                                  sp)
                    reports[gname]["cpu_util"] = cap
        return reports

    return fn


def dropout_report_fn(dead: Dict[str, Tuple[int, int]]) -> Callable:
    """dead: {group: (fail_step, rejoin_step)} -> silent groups (heartbeat
    path)."""

    def fn(step, plan, dt):
        reports = HeteroTrainer._healthy_reports(plan)
        for gname, (s, e) in dead.items():
            if s <= step < e:
                reports.pop(gname, None)
        return reports

    return fn


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------


def _parse_groups(text: str, sm: SpeedModel) -> Dict[str, Tuple]:
    out = {}
    for part in text.split(","):
        name, count = part.split(":")
        out[name] = (int(count), sm)
    return out


def parse_interfere(text: Optional[str]):
    """The ``--interfere`` grammar -> simulator event dataclasses.

    part := GROUP@START[-END]EFFECT, EFFECT one of
      x<frac>   capacity scale (the historical form; END optional)
      v<img/s>  absolute speed cap (core-stealing bound)
      !         dropout: the group publishes nothing in the window

    Returns (interferences, dropouts) — the SAME dataclasses ClusterSim
    and the runtime's WorkerSpecs consume, so one schedule string drives
    all three execution substrates identically.
    """
    from repro.core.simulator import Dropout, Interference

    ivs: List[Interference] = []
    drops: List[Dropout] = []
    if not text:
        return ivs, drops
    for part in text.split(","):
        name, rest = part.split("@")
        m = re.match(r"^(\d+)(?:-(\d+))?(x[\d.eE+-]+|v[\d.eE+-]+|!)$", rest)
        if not m:
            raise ValueError(f"bad --interfere event: {part!r}")
        start = int(m.group(1))
        end = int(m.group(2)) if m.group(2) else 10 ** 9
        effect = m.group(3)
        if effect == "!":
            drops.append(Dropout(name, start, end))
        elif effect.startswith("x"):
            ivs.append(Interference(name, start, end,
                                    capacity=float(effect[1:])))
        else:
            ivs.append(Interference(name, start, end,
                                    speed_cap=float(effect[1:])))
    return ivs, drops


def events_report_fn(interferences, dropouts) -> Optional[Callable]:
    """Report hook for the inproc loop from simulator event dataclasses:
    capacity-scaled + absolutely-capped speeds (``ClusterSim`` model),
    dropped-out groups silent."""
    if not interferences and not dropouts:
        return None

    from repro.core.interference import (govern_speed, window_capacity,
                                         window_speed_cap)

    def fn(step, plan, dt):
        reports = HeteroTrainer._healthy_reports(plan)
        for d in dropouts:
            if d.start_step <= step < d.end_step:
                reports.pop(d.group, None)
        for g in plan.groups:
            if g.name not in reports or g.batch_size <= 0:
                continue
            cap = window_capacity(interferences, step, g.name)
            if cap >= 1.0 and \
                    window_speed_cap(interferences, step, g.name) is None:
                continue
            sp = govern_speed(g.speed_model.speed(g.batch_size),
                              interferences, step, g.name)
            reports[g.name]["speed"] = min(reports[g.name]["speed"], sp)
            reports[g.name]["cpu_util"] = cap
        return reports

    return fn


def _train_in_workers(args) -> bool:
    return (args.worker_train == "on"
            or (args.worker_train == "auto"
                and args.runtime in ("process", "socket")))


def _run_distributed(args, cfg: TrainerConfig, sm: SpeedModel,
                     interferences, dropouts) -> None:
    """Drive training through the Stannis runtime (repro.runtime): a
    coordinator EventLoop + thread or process workers over typed IPC.

    Diagnostics route through an :class:`EventLog` (DESIGN.md §14):
    human-readable lines on stderr, the same events into the trace sink
    when ``--trace`` is on. The lines scripts consume — the socket
    coordinator's "listening on" line, the per-group join commands and
    the cluster map — stay on stdout, unchanged."""
    from repro.checkpoint.checkpointer import RunJournal
    from repro.runtime import EventLoop, FaultAction, MANAGERS, \
        specs_from_plan
    from repro.runtime.ipc import ChaosSpec

    tracer = (Tracer(source="coord", sinks=[ChromeTraceSink(args.trace)])
              if args.trace else None)
    metrics = (MetricsRegistry() if args.trace or args.metrics_every
               else None)
    log = EventLog(tracer)
    if cfg.ckpt_dir or args.resume:
        # runtime CheckpointAcks are state summaries, not on-disk
        # snapshots (param fan-in is a ROADMAP open item)
        log.warn("ckpt_unsupported",
                 "warning: --ckpt-dir/--resume are inproc-only; the "
                 f"{args.runtime} runtime does not persist checkpoints yet",
                 runtime=args.runtime)
    plan = allocator.solve(_parse_groups(args.groups, sm), cfg.dataset_size)
    train_workers = _train_in_workers(args)
    train = ({"arch": args.arch, "seq_len": args.seq_len,
              "reduced": not args.full_size} if train_workers else None)
    cp = ControlPlane(plan, [policy_from_config(cfg.hypertune)],
                      cfg=cfg.hypertune, liveness_timeout=3)
    # chaos plane (DESIGN.md §15): the spec seeds per-link fault
    # injectors inside the managers; its partition windows become
    # round-exact partition/heal fault actions so ClusterSim can mirror
    # each one as a Dropout of the same steps
    chaos = ChaosSpec.parse(args.chaos) if args.chaos else None
    faults: List[FaultAction] = []
    if chaos is not None:
        for p in chaos.partitions:
            faults.append(FaultAction(p.start_step, "partition", p.group))
            faults.append(FaultAction(p.end_step, "heal", p.group))
    if args.runtime == "socket":
        from repro.runtime import SocketExecutionManager

        manager = SocketExecutionManager(listen=args.listen,
                                         spawn=not args.external_workers,
                                         chaos=chaos)
        print(f"coordinator listening on {manager.endpoint}", flush=True)
        if args.external_workers:
            print("waiting for standalone workers — one per group, on "
                  "any host:", flush=True)
            for g in plan.batch_sizes():
                print(f"  python -m repro.launch.worker "
                      f"--connect {manager.advertised} --group {g}",
                      flush=True)
    else:
        manager = MANAGERS[args.runtime](chaos=chaos)
    # training workers jit-compile on their first granted step; a short
    # round deadline would read that compile stall as bus silence and
    # mask healthy groups out, so the auto default is generous
    round_timeout = (args.round_timeout if args.round_timeout is not None
                     else (120.0 if train_workers else 5.0))
    loop = EventLoop(cp, manager, round_timeout=round_timeout,
                     staleness=args.staleness, tracer=tracer,
                     metrics=metrics, metrics_every=args.metrics_every)
    # crash-resume journal (DESIGN.md §15): --journal-dir records run
    # state every N rounds; --resume-run restores the newest intact
    # entry and continues granting at the journaled round
    journal_dir = args.resume_run or args.journal_dir
    journal = RunJournal(journal_dir) if journal_dir else None
    start = 0
    if args.resume_run:
        state = journal.load_latest()
        if state is None:
            log.warn("resume_empty",
                     f"--resume-run {args.resume_run}: no usable journal "
                     "entry; starting from round 0",
                     run_dir=args.resume_run)
        else:
            start = loop.restore(state)
            log.info("resume_run",
                     f"resuming at round {start} from {journal_dir} "
                     f"(plan {cp.plan.batch_sizes()})",
                     run_dir=journal_dir, next_round=start)
    log.info("runtime_start",
             f"runtime={args.runtime} workers={cp.plan.batch_sizes()} "
             f"train_in_workers={train_workers} staleness={args.staleness}",
             runtime=args.runtime, staleness=args.staleness,
             train_in_workers=train_workers)
    try:
        # start() inside the try: a handshake failure on worker N must
        # still tear down workers 0..N-1. On resume the workers come up
        # with the JOURNALED plan's batch sizes (cp.plan after restore).
        manager.start(specs_from_plan(cp.plan, interferences, dropouts,
                                      train=train, seed=cfg.seed,
                                      obs=tracer is not None))
        res = loop.run(args.steps, faults=faults, checkpoint_every=10,
                       journal=journal, journal_every=args.journal_every,
                       start=start)
    finally:
        loop.shutdown()
        if tracer is not None:
            tracer.close()
    log.info("runtime_done",
             f"done: {res.rounds} rounds, {res.reports_total} reports "
             f"({res.reports_per_s:.0f} reports/s, "
             f"{res.mean_round_latency_s * 1e3:.1f} ms/round), "
             f"{len(res.events)} plan changes",
             rounds=res.rounds, reports=res.reports_total,
             retunes=len(res.events))
    for e in res.events:
        log.info("retune",
                 f"  retune @ round {e.step}: {e.group}:"
                 f"{e.old_batch}->{e.new_batch} ({e.reason})")
    if res.retune_lags:
        log.info("retune_lags",
                 f"  retune propagation lag: {res.retune_lags} round(s)")
    if res.staleness:
        log.info("staleness",
                 f"  bounded staleness k={res.staleness}: "
                 f"{res.stale_reports} stale report(s) dropped")
    if res.hosts:
        # the cluster map is a script-consumed contract: stdout
        for g, where in sorted(res.hosts.items()):
            print(f"  group {g}: {where}")
    for ack in res.checkpoint_acks[-len(plan.groups):]:
        log.info("worker_final",
                 f"  worker {ack.group}: step {ack.worker_step} "
                 f"b={ack.batch_size} compiles={ack.n_compiles}")
    if metrics is not None:
        log.info("metrics_summary", metrics.summary_line("[metrics] "))
    if args.trace:
        log.info("trace_written",
                 f"trace written to {args.trace} — summarize with: "
                 f"python -m repro.launch.obs summarize {args.trace}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--full-size", action="store_true",
                    help="use the full config (default: reduced, CPU-safe)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq-len", type=int, default=64)
    ap.add_argument("--groups", default="host:1,worker:2")
    ap.add_argument("--interfere", default=None,
                    help="e.g. 'csd@20-40x0.5,csd@45-50!' (x=capacity, "
                         "v=absolute img/s cap, !=dropout)")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--runtime",
                    choices=("inproc", "local", "process", "socket"),
                    default="inproc",
                    help="inproc: single-process loop; local: thread "
                         "workers; process: real worker processes; "
                         "socket: TCP mesh (multi-host capable)")
    ap.add_argument("--listen", default="127.0.0.1:0", metavar="HOST:PORT",
                    help="coordinator endpoint for --runtime socket "
                         "(port 0 = ephemeral; bind 0.0.0.0 for real "
                         "multi-host runs)")
    ap.add_argument("--external-workers", action="store_true",
                    help="with --runtime socket: spawn nothing and wait "
                         "for standalone workers (python -m "
                         "repro.launch.worker --connect) to join")
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded-staleness bound k for the runtime "
                         "coordinator: keep up to k rounds of grants in "
                         "flight per worker (0 = strict synchronous "
                         "rendezvous, the Fig. 6 parity mode)")
    ap.add_argument("--round-timeout", type=float, default=None,
                    help="coordinator round deadline (s); a silent worker "
                         "costs at most this per round (default: 5, or 120 "
                         "when workers run jitted steps)")
    ap.add_argument("--worker-train", choices=("auto", "on", "off"),
                    default="auto",
                    help="run real jitted steps inside runtime workers "
                         "(auto: on for --runtime process)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write the run timeline (inproc: the trainer's "
                         "per-step spans; runtimes: coordinator + worker "
                         "spans, retune rationale) as Chrome trace-event "
                         "JSON — open in https://ui.perfetto.dev or "
                         "summarize with python -m repro.launch.obs")
    ap.add_argument("--metrics-every", type=int, default=0, metavar="N",
                    help="print a one-line metrics summary (round "
                         "latency quantiles, report/retune counters) "
                         "every N coordinator rounds")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="seeded network-fault injection on every worker "
                         "link, e.g. 'seed=7,drop=0.01,send.dup=0.02,"
                         "window=5-25:recv.drop=0.2,partition=xeon1@20-26'"
                         " (DESIGN.md §15); activates the reliable "
                         "session layer so the run still completes "
                         "exactly")
    ap.add_argument("--journal-dir", default=None, metavar="DIR",
                    help="journal coordinator run state under DIR/journal "
                         "so a killed coordinator can --resume-run DIR")
    ap.add_argument("--journal-every", type=int, default=1, metavar="N",
                    help="journal every N coordinator rounds (default 1)")
    ap.add_argument("--search", type=int, default=0, metavar="N",
                    help="race N sampled trial configs (lr/batch/arch) "
                         "under an ASHA pruner instead of training one "
                         "model: one worker group per trial on the "
                         "selected runtime, pruned trials' capacity "
                         "re-granted to survivors (full control: python "
                         "-m repro.launch.search)")
    ap.add_argument("--search-seed", type=int, default=0, metavar="S",
                    help="with --search: the search is a pure function "
                         "of this seed")
    ap.add_argument("--resume-run", default=None, metavar="DIR",
                    help="restart a killed coordinator from DIR's newest "
                         "intact journal entry: restore the tuned plan + "
                         "policy state, re-admit workers, continue the "
                         "run at the journaled round (keeps journaling "
                         "to the same DIR)")
    args = ap.parse_args()
    if args.staleness and args.runtime == "inproc":
        # the inproc loop has no grant pipeline to run ahead on —
        # silently training synchronously would misreport the mode
        ap.error("--staleness requires a runtime with a coordinator "
                 "grant pipeline; use --runtime local or --runtime "
                 "process")
    if args.staleness < 0:
        ap.error("--staleness must be >= 0")
    if args.runtime == "inproc" and args.metrics_every:
        ap.error("--metrics-every instruments the runtime coordinator; "
                 "use --runtime local, process or socket")
    if args.metrics_every < 0:
        ap.error("--metrics-every must be >= 0")
    if args.runtime != "socket":
        if args.external_workers:
            ap.error("--external-workers requires --runtime socket")
        if args.listen != "127.0.0.1:0":
            ap.error("--listen requires --runtime socket")
    if args.runtime == "inproc" and (args.chaos or args.journal_dir
                                     or args.resume_run):
        ap.error("--chaos/--journal-dir/--resume-run drive the runtime "
                 "coordinator; use --runtime local, process or socket")
    if args.journal_every < 1:
        ap.error("--journal-every must be >= 1")
    if args.resume_run and args.journal_dir \
            and args.resume_run != args.journal_dir:
        ap.error("--resume-run and --journal-dir must agree (resume "
                 "keeps journaling to the same run directory)")
    if args.search:
        if args.search < 2:
            ap.error("--search needs >= 2 trials to race")
        if args.runtime == "inproc":
            ap.error("--search races one worker group per trial on the "
                     "runtime coordinator; use --runtime local, process "
                     "or socket")
        if (args.interfere or args.ckpt_dir or args.resume or args.chaos
                or args.journal_dir or args.resume_run
                or args.external_workers):
            ap.error("--search is a self-contained race; it does not "
                     "combine with --interfere/--ckpt-dir/--resume/"
                     "--chaos/--journal-dir/--resume-run/"
                     "--external-workers")
        # branch before the probe bootstrap: a search run needs no
        # jitted warm-up, only the calibrated trial speed curves
        from repro.launch.search import main as search_main
        argv = ["--trials", str(args.search),
                "--seed", str(args.search_seed),
                "--steps", str(args.steps),
                "--runtime", args.runtime,
                "--staleness", str(args.staleness)]
        if args.round_timeout is not None:
            argv += ["--round-timeout", str(args.round_timeout)]
        raise SystemExit(search_main(argv))

    enable_compile_cache()
    arch = get_arch(args.arch)
    if not args.full_size:
        arch = reduced_config(arch)
    cfg = TrainerConfig(steps=args.steps, seq_len=args.seq_len,
                        ckpt_dir=args.ckpt_dir,
                        ckpt_every=10 if args.ckpt_dir else 0)
    interferences, dropouts = parse_interfere(args.interfere)

    if args.runtime != "inproc":
        train_workers = _train_in_workers(args)
        if train_workers and args.runtime in ("process", "socket"):
            # one process per chip: refuse a run whose training workers
            # cannot each own one, and keep the coordinator off JAX —
            # the probe runs in a child that exits before workers spawn
            if not args.external_workers:
                try:
                    check_chip_budget(len(args.groups.split(",")),
                                      host_tpu_chips())
                except ValueError as e:
                    ap.error(str(e))
            sm = probe_in_child(arch, cfg)
        else:
            sm = HeteroTrainer.for_probe(arch, cfg).probe_speed_model()
        _log_probe(sm)
        _run_distributed(args, cfg, sm, interferences, dropouts)
        return

    tracer = (Tracer(source="train", sinks=[ChromeTraceSink(args.trace)],
                     annotate=jax.profiler.TraceAnnotation)
              if args.trace else NULL_TRACER)
    try:
        trainer = train_inproc(arch, cfg, args.groups, interferences,
                               dropouts, resume=args.resume, tracer=tracer)
    finally:
        tracer.close()
    recs = trainer.records
    retunes = [r for r in recs if r.retune]
    LOG.info("inproc_done",
             f"done: {len(recs)} steps, {len(retunes)} retunes, "
             f"final loss {recs[-1].loss:.4f}",
             steps=len(recs), retunes=len(retunes), loss=recs[-1].loss)
    for r in retunes:
        LOG.info("retune", f"  retune @ step {r.step}: {r.retune}")
    if args.trace:
        LOG.info("trace_written",
                 f"trace written to {args.trace} — summarize with: "
                 f"python -m repro.launch.obs summarize {args.trace}")


def _log_probe(sm: SpeedModel) -> None:
    LOG.info("probe", f"probe: knee={sm.knee()} vmax={sm.vmax:.2f} samp/s",
             knee=float(sm.knee()), vmax=float(sm.vmax))


def train_inproc(arch: ArchConfig, cfg: TrainerConfig, groups: str,
                 interferences=(), dropouts=(), *, resume: bool = False,
                 batch_ladder=PROBE_LADDER,
                 tracer=NULL_TRACER) -> HeteroTrainer:
    """The inproc main path: probe this node, allocate the groups with
    Eq. 1 from that one curve (single-host stand-in; a fleet probes per
    node class), then train ``cfg.steps`` capacity-masked steps under the
    ``--interfere`` schedule, traced by ``tracer``. Returns the trainer
    (``records`` holds the steps). The probing trainer's state becomes
    the training state, so one copy of the model state exists."""
    bootstrap = HeteroTrainer.for_probe(arch, cfg)
    sm = bootstrap.probe_speed_model(batch_ladder)
    _log_probe(sm)
    trainer = HeteroTrainer.from_probe(
        arch, _parse_groups(groups, sm), cfg,
        state=(bootstrap.params, bootstrap.opt_state))
    del bootstrap
    if resume and trainer.resume():
        LOG.info("resume", f"resumed at step {trainer.step}",
                 step=trainer.step)
    trainer.tracer = tracer
    trainer.run(report_fn=events_report_fn(interferences, dropouts))
    return trainer


def _probe_child(arch: ArchConfig, cfg: TrainerConfig, conn) -> None:
    enable_compile_cache()
    sm = HeteroTrainer.for_probe(arch, cfg).probe_speed_model()
    conn.send((sm.batch_sizes.tolist(), sm.speeds.tolist()))
    conn.close()


def probe_in_child(arch: ArchConfig, cfg: TrainerConfig) -> SpeedModel:
    """Probe this node in a spawn-context child (on chip 0 of a TPU
    host) that exits before the caller starts training workers, so the
    caller never holds a chip."""
    import multiprocessing

    ctx = multiprocessing.get_context("spawn")
    recv, send = ctx.Pipe(duplex=False)
    env = one_chip_env(0) if host_tpu_chips() else {}
    proc = ctx.Process(target=run_with_env,
                       args=(env, _probe_child, arch, cfg, send),
                       name="stannis-probe")
    proc.start()
    send.close()
    try:
        batches, speeds = recv.recv()
    except EOFError:
        proc.join()
        raise RuntimeError(f"node probe process failed (exit code "
                           f"{proc.exitcode})") from None
    proc.join()
    return SpeedModel(np.asarray(batches), np.asarray(speeds))


if __name__ == "__main__":
    main()
