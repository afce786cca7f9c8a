"""Distributed Stannis: coordinator + real worker processes, end to end.

  phase 1 — trace parity: the paper's Fig. 6 escalating-interference
            scenario (Gzip steals 4/8 then 6/8 cores of one Xeon) runs
            through live workers under the coordinator EventLoop and
            reproduces the EXACT 180 -> 140 -> 100 retune sequence the
            calibrated ClusterSim produces. Interference is injected
            worker-side (speed governor), decisions flow back as typed
            Retune messages.

  phase 2 — real training + real faults: two groups of worker processes
            each run the jitted train step (hetero_dp.make_train_step)
            at their live batch size, streaming reports over pipes. One
            worker is SIGKILLed mid-run: the coordinator observes
            genuine bus silence, masks the group out (b_g -> 0), a
            restarted worker rejoins at its benchmark knee — and the
            workers never recompile (CheckpointAck.n_compiles == 1).
            This process never touches JAX (the plan comes from a fixed
            curve); on a TPU host the manager gives each training worker
            a chip of its own, so phase 2 needs two chips and is refused
            up front on a one-chip host.

  PYTHONPATH=src python examples/distributed_stannis.py [--steps 12]
      [--runtime process|local|socket] [--staleness K]
      [--codec auto|json|binary|msgpack] [--skip-train]

``--runtime socket`` runs the same two phases with the coordinator and
workers speaking length-prefixed frames over real TCP connections (the
multi-host mesh backend); ``--staleness K`` runs both phases under
bounded-staleness pacing (grants pipelined K rounds ahead); ``--codec``
caps the socket wire codec instead of letting the rendezvous negotiate
the best one (``--codec json`` is the old-worker compatibility canary,
DESIGN.md §13). The CI matrix exercises every (runtime, staleness)
cell — plus the socket binary-codec and json-canary cells — under its
own hard timeout so a transport-specific hang names its cell.
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.core.allocator import solve
from repro.core.control import ControlPlane, SpeedDeclinePolicy
from repro.core.speed_model import SpeedModel
from repro.obs import MetricsRegistry
from repro.runtime import EventLoop, FaultAction, MANAGERS, specs_from_plan
from repro.runtime.parity import fig6_chaos_parity, fig6_parity


def _round_stats_line(metrics: MetricsRegistry) -> str:
    """Round/lag stats straight from the run's registry (DESIGN.md §14)
    — the single numeric source of truth, not re-derived ad hoc."""
    lat = metrics.get("coord.round_latency_s")
    parts = []
    if lat is not None and lat.count:
        parts.append(f"round p50={lat.quantile(0.5) * 1e3:.2f}ms "
                     f"p99={lat.quantile(0.99) * 1e3:.2f}ms")
    lag = metrics.get("coord.retune_effect_lag_rounds")
    if lag is not None and lag.count:
        parts.append(f"retune effect lag p50={lag.quantile(0.5):.0f} "
                     f"rounds")
    reps = metrics.get("coord.reports")
    if reps is not None:
        parts.append(f"reports={reps.value}")
    return "  " + " | ".join(parts) if parts else ""


def phase1_trace_parity(runtime: str, staleness: int,
                        mgr_kwargs: dict = {}, tracer=None,
                        chaos=None) -> None:
    print(f"— phase 1: Fig. 6 trace parity through {runtime} workers "
          f"(staleness k={staleness}"
          + (f", codec={mgr_kwargs['codec']}" if "codec" in mgr_kwargs
             else "")
          + (f", chaos={chaos!r}" if chaos else "") + ") —")
    metrics = MetricsRegistry()
    if chaos:
        # seeded frame loss/dup/reorder healed by the reliable session
        # must leave the event stream bit-identical to the clean sim;
        # a partition window in the spec mirrors as a sim Dropout
        p = fig6_chaos_parity(manager=runtime, staleness=staleness,
                              chaos=chaos, manager_kwargs=mgr_kwargs,
                              tracer=tracer, metrics=metrics)
    else:
        p = fig6_parity(manager=runtime, staleness=staleness,
                        manager_kwargs=mgr_kwargs, tracer=tracer,
                        metrics=metrics)
    print(f"  sim     : {p['sim']}")
    print(f"  runtime : {p['runtime']}")
    assert p["match"], "runtime diverged from the simulator trace"
    if not chaos:
        assert p["result"].retune_lags == [staleness + 1] * 2, \
            f"retune lag {p['result'].retune_lags} != k+1={staleness + 1}"
    # the paper's worked-example sequence reads off the DECLINE retunes
    # (a chaos partition adds failure/recover events around them)
    declines = [e for e in p["runtime"] if e[4] == "decline"]
    seq = [e[2] for e in declines] + [declines[-1][3]]
    print(f"  retune sequence {' -> '.join(map(str, seq))}  "
          f"(paper §III-B worked example)  "
          f"[lag {p['result'].retune_lags} round(s)]")
    print(_round_stats_line(metrics))
    if p["result"].hosts:
        print(f"  cluster map: {p['result'].hosts}")


def phase2_live_training(runtime: str, steps: int,
                         staleness: int = 0,
                         mgr_kwargs: dict = {}, tracer=None) -> None:
    print(f"\n— phase 2: real jitted training in {runtime} workers, "
          f"kill + rejoin (staleness k={staleness}) —")
    sm = SpeedModel(np.array([1.0, 2, 4, 8]), np.array([10.0, 18, 28, 30]))
    plan = solve({"a": (1, sm), "b": (1, sm)}, dataset_size=4096)
    cp = ControlPlane(plan, [SpeedDeclinePolicy()], liveness_timeout=3)
    metrics = MetricsRegistry()
    specs = specs_from_plan(
        plan, train={"arch": "deepseek-7b", "seq_len": 32, "reduced": True},
        obs=tracer is not None)
    faults = []
    # under run-ahead the dead worker may have pre-delivered up to k
    # reports, deferring silence-derived detection by at most k rounds —
    # the restart must land after the latest possible failure round
    # (kill + k + liveness_timeout) or the rejoin would mask the failure
    # it is supposed to recover from; when the run is too short to fit
    # that window (plus a round for the recover event), skip the fault
    # injection rather than schedule one that cannot be detected
    restart_floor = 3 + staleness + 3    # kill step + k + liveness
    if steps >= restart_floor + 2:
        restart = min(max(steps - 4, restart_floor), steps - 2)
        faults = [FaultAction(3, "kill", "b"),
                  FaultAction(restart, "restart", "b")]
    else:
        print(f"  (steps={steps} too short for kill+rejoin at "
              f"staleness {staleness}; skipping fault injection)")
    manager = MANAGERS[runtime](**mgr_kwargs)
    loop = EventLoop(cp, manager, round_timeout=120.0,
                     staleness=staleness, tracer=tracer, metrics=metrics)
    try:
        manager.start(specs)
        res = loop.run(steps, faults=faults,
                       checkpoint_every=max(steps - 1, 1))
    finally:
        loop.shutdown()
    print(f"  {res.rounds} rounds, {res.reports_total} reports, "
          f"plan changes: {res.event_tuples()}")
    print(_round_stats_line(metrics))
    if faults:
        reasons = [e.reason for e in res.events]
        assert "failure" in reasons, "kill was not detected via silence"
        assert "recover" in reasons, "restarted worker did not rejoin"
    for ack in res.checkpoint_acks:
        print(f"  worker {ack.group}: step {ack.worker_step} "
              f"b={ack.batch_size} compiles={ack.n_compiles}")
        assert ack.n_compiles <= 1, "retune caused a recompile"
    print("OK")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--runtime", choices=("local", "process", "socket"),
                    default="process")
    ap.add_argument("--steps", type=int, default=12)
    ap.add_argument("--staleness", type=int, default=0,
                    help="bounded-staleness bound k (0 = synchronous "
                         "rendezvous)")
    ap.add_argument("--codec", default="auto",
                    choices=("auto", "json", "binary", "msgpack"),
                    help="cap the socket wire codec (auto = negotiate "
                         "the best both ends speak; json = the "
                         "old-worker compatibility canary)")
    ap.add_argument("--chaos", default=None, metavar="SPEC",
                    help="run phase 1 under seeded network chaos, e.g. "
                         "'seed=7,drop=0.02,dup=0.02,partition="
                         "xeon1@20-26' — the Fig. 6 sequence must "
                         "still match the simulator exactly")
    ap.add_argument("--skip-train", action="store_true",
                    help="protocol/parity phase only (no jitted steps)")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="write both phases' merged run timeline as "
                         "Chrome trace-event JSON (Perfetto-loadable)")
    args = ap.parse_args()
    mgr_kwargs = {}
    if args.codec != "auto":
        if args.runtime != "socket":
            ap.error("--codec applies to --runtime socket only (the "
                     "in-process transports exchange objects, not "
                     "framed bytes)")
        mgr_kwargs = {"codec": args.codec}
    tracer = None
    if args.trace:
        from repro.obs import ChromeTraceSink, Tracer
        tracer = Tracer(source="coord",
                        sinks=[ChromeTraceSink(args.trace)])
    try:
        phase1_trace_parity(args.runtime, args.staleness, mgr_kwargs,
                            tracer=tracer, chaos=args.chaos)
        if not args.skip_train:
            phase2_live_training(args.runtime, args.steps, args.staleness,
                                 mgr_kwargs, tracer=tracer)
    finally:
        if tracer is not None:
            tracer.close()
            print(f"trace written to {args.trace}")


if __name__ == "__main__":
    main()
