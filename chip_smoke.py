#!/usr/bin/env python3
"""Smoke run of the training main path on TPU chips.

Default (one chip): the inproc path of ``python -m repro.launch.train``,
through the same function (``train_inproc``): probe this node, allocate
the groups with Eq. 1, take capacity-masked jitted steps, and retune an
interfered group by changing its row mask without recompiling. The model
is deepseek-7b at its published widths (d_model 4096, 32 query and 32 KV
heads of 128, d_ff 11008, bf16) with random weights from ``--seed``, cut
to 2 layers and to a 12,800-row slice of its 102,400-token vocabulary
(token ids are drawn from the slice and the loss is over it).

``--four-chips``: the Stannis process runtime with four training groups,
one worker process per chip, one group interfered; then group 0's spec
alone, in a fresh runtime on one chip. Its losses must equal the
four-chip run's group-0 losses. No other phase runs with this option.

Exits non-zero, with no result line, unless JAX finds a TPU and every
check holds. The last stdout line is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Timings printed on the way are one smoke run, not a benchmark.

    python chip_smoke.py [--seed 0] [--four-chips]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import pathlib
import statistics
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

# the cut of deepseek-7b both phases train (widths stay published)
LAYERS = 2                # the dense decoder's layer period is 1
VOCAB = 12_800            # one eighth of 102,400
SEQ_LEN = 256             # with <= 12 capacity rows, leaves > 2 GB of HBM
PROBE_LADDER = (1, 2, 4)  # knee <= 4 rows per node -> capacity <= 12 rows
GROUPS = "host:1,worker:2"
INTERFERE = "worker@4x0.4"
STEPS = 12
LOSS_WINDOW = 1.0         # first loss within this of ln(VOCAB)
HBM_LIMIT = 16e9          # one v5e chip
BF16_RTOL = 2.0 ** -7     # two bf16 ulps
FOUR_CHIP_ROUNDS = 10


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def smoke_arch():
    from repro.configs.base import get_arch

    full = get_arch("deepseek-7b")
    arch = dataclasses.replace(full, num_layers=LAYERS, vocab_size=VOCAB)
    print(f"config: {full.name} widths d_model={arch.d_model} "
          f"heads={arch.num_heads}q/{arch.num_kv_heads}kv x "
          f"{arch.resolved_head_dim} d_ff={arch.d_ff} {arch.dtype}; "
          f"{arch.param_count() / 1e6:.1f}M params", flush=True)
    print(f"cut: layers {full.num_layers} -> {LAYERS}; vocab "
          f"{full.vocab_size} -> {VOCAB} (ids drawn from the slice); "
          f"seq_len {SEQ_LEN}; probe ladder {PROBE_LADDER} -> capacity "
          f"<= {PROBE_LADDER[-1] * 3} rows", flush=True)
    return full, arch


def worker_train_spec() -> dict:
    """The same cut, as a runtime worker's ``WorkerSpec.train``."""
    return {"arch": "deepseek-7b", "seq_len": SEQ_LEN, "reduced": False,
            "overrides": {"num_layers": LAYERS, "vocab_size": VOCAB}}


def tpu_device():
    """The first JAX device, or SmokeFailure when it is not a TPU."""
    import jax

    dev = jax.devices()[0]
    check(dev.platform == "tpu",
          f"no TPU found: JAX reports platform {dev.platform!r}")
    return dev, len(jax.devices())


def compile_clock():
    """Seconds spent in XLA backend compiles, and persistent-cache hits,
    as JAX's monitoring events report them."""
    from jax import monitoring

    totals = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return totals


def cache_files(path) -> str:
    if not path:
        return "off"
    n = sum(1 for p in pathlib.Path(path).rglob("*") if p.is_file()) \
        if os.path.isdir(path) else 0
    return f"{path} ({n} files)"


def one_chip(seed: int) -> dict:
    import jax
    import numpy as np

    from repro.accel import enable_compile_cache
    from repro.launch.train import (TrainerConfig, parse_interfere,
                                    train_inproc)

    dev, count = tpu_device()
    print(f"device: {dev.platform} {dev.device_kind} (x{count})", flush=True)
    cache = enable_compile_cache()
    clock = compile_clock()
    _, arch = smoke_arch()
    cfg = TrainerConfig(steps=STEPS, seq_len=SEQ_LEN, seed=seed,
                        log_every=0)
    interferences, dropouts = parse_interfere(INTERFERE)

    t0 = time.perf_counter()
    trainer = train_inproc(arch, cfg, GROUPS, interferences, dropouts,
                           batch_ladder=PROBE_LADDER)
    wall = time.perf_counter() - t0
    sm = trainer.plan.groups[0].speed_model
    print("probe curve (rows -> rows/s): " + ", ".join(
        f"{int(b)} -> {s:.2f}" for b, s in zip(sm.batch_sizes, sm.speeds)),
        flush=True)
    print(f"plan: {trainer.plan.batch_sizes()} "
          f"(capacity {trainer.plan.global_capacity} rows x {SEQ_LEN})",
          flush=True)
    recs = trainer.records
    for r in recs:
        print(f"  step {r.step:2d} loss {r.loss:.4f} "
              f"global batch {r.global_batch}"
              + (f"  retune {r.retune}" if r.retune else ""), flush=True)

    # step time on the host clock around the step and
    # block_until_ready, on the trainer's own compiled step
    np_batch = trainer.pipeline.next_batch()
    batch = {k: jax.numpy.asarray(np_batch[k])
             for k in ("tokens", "targets", "sample_mask")}
    times = []
    for _ in range(3):
        t = time.perf_counter()
        jax.block_until_ready(trainer.step_fn(trainer.params,
                                              trainer.opt_state, batch))
        times.append(time.perf_counter() - t)
    stats = dev.memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    n_compiles = trainer.step_fn._cache_size()
    print(f"compile: {clock['compile_s']:.1f} s in XLA compiles, "
          f"{clock['cache_hits']} persistent-cache hit(s); cache "
          f"{cache_files(cache)}", flush=True)
    print(f"wall (probe + {len(recs)} steps, compiles included): "
          f"{wall:.1f} s", flush=True)
    print(f"median step (one smoke run, not a benchmark): "
          f"{statistics.median(times) * 1e3:.1f} ms over {len(times)} "
          f"steps of {trainer.plan.global_capacity} x {SEQ_LEN} rows",
          flush=True)
    print(f"peak HBM: {peak} bytes of {stats.get('bytes_limit')}",
          flush=True)
    print(f"step_fn compiles: {n_compiles}", flush=True)

    losses = [r.loss for r in recs]
    retunes = [r for r in recs if r.retune]
    check(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    check(abs(losses[0] - math.log(VOCAB)) <= LOSS_WINDOW,
          f"first loss {losses[0]:.4f} not within {LOSS_WINDOW} of "
          f"ln({VOCAB}) = {math.log(VOCAB):.4f}")
    check(len(retunes) >= 1, "no retune happened")
    check(n_compiles == 1, f"step_fn compiled {n_compiles} programs "
                           f"across the retune (want 1)")
    check(peak is not None and peak < HBM_LIMIT,
          f"peak HBM {peak} bytes not under {HBM_LIMIT:.0f}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


def _run_runtime(groups, interferences, train, seed, rounds):
    """One Stannis process-runtime run; returns (result, worker states)."""
    from repro.core.allocator import solve
    from repro.core.control import ControlPlane, SpeedDeclinePolicy
    from repro.core.speed_model import SpeedModel
    from repro.runtime import EventLoop, ProcessManager, specs_from_plan
    from repro.runtime.ipc.shm import bulk_bytes

    # a fixed curve with its knee at 4 rows, so every group trains 4
    # capacity rows (the parent does not probe: it stays off the chips)
    sm = SpeedModel([1.0, 2.0, 4.0], [1.0, 1.8, 2.6])
    plan = solve({g: (1, sm) for g in groups}, dataset_size=4096)
    cp = ControlPlane(plan, [SpeedDeclinePolicy()], liveness_timeout=3)
    manager = ProcessManager(hello_timeout=600.0)
    # workers compile on their first grant; four compile side by side
    loop = EventLoop(cp, manager, round_timeout=900.0)
    try:
        manager.start(specs_from_plan(plan, interferences, train=train,
                                      seed=seed))
        res = loop.run(rounds, checkpoint_every=rounds - 1)
    finally:
        loop.shutdown()
    states = {}
    for ack in res.checkpoint_acks:
        states[ack.group] = json.loads(bulk_bytes(ack.state))
    return res, states


def four_chips(seed: int) -> dict:
    import numpy as np

    from repro.accel import enable_compile_cache, host_tpu_chips
    from repro.core.simulator import Interference

    chips = host_tpu_chips()
    check(chips >= 4, f"--four-chips needs 4 TPU chips on this host; "
                      f"found {chips}")
    cache = enable_compile_cache()       # touches no backend
    smoke_arch()
    train = worker_train_spec()
    groups = [f"g{i}" for i in range(4)]

    t0 = time.perf_counter()
    # workers report their measured speed, far above the fixed curve's;
    # an absolute cap below the plan's 2.6 rows/s slows g3 for certain,
    # and the speed-decline policy retunes it 4 -> 1
    res, states = _run_runtime(
        groups, [Interference("g3", 2, 10 ** 9, speed_cap=1.0)], train,
        seed, FOUR_CHIP_ROUNDS)
    print(f"four workers: {res.rounds} rounds in "
          f"{time.perf_counter() - t0:.1f} s (compiles included), plan "
          f"changes {res.event_tuples()}", flush=True)
    for g in groups:
        st = states.get(g, {})
        print(f"  {g}: device {st.get('device')} compiles "
              f"{st.get('n_compiles')} losses {st.get('losses')}",
              flush=True)

    t0 = time.perf_counter()
    _, ref_states = _run_runtime(["g0"], [], train, seed, FOUR_CHIP_ROUNDS)
    ref = ref_states.get("g0", {})
    print(f"g0 alone on one chip: {time.perf_counter() - t0:.1f} s, "
          f"device {ref.get('device')} losses {ref.get('losses')}",
          flush=True)
    print(f"compile cache: {cache_files(cache)}", flush=True)

    check(sorted(states) == groups, f"acks from {sorted(states)}")
    devs = [states[g]["device"] for g in groups]
    check(all(d["platform"] == "tpu" and d["count"] == 1 for d in devs),
          f"each worker must see exactly one TPU: {devs}")
    ids = {(d["visible_chips"], d["id"], tuple(d["coords"])) for d in devs}
    check(len(ids) == 4, f"workers do not hold four different chips: "
                         f"{devs}")
    check(all(states[g]["n_compiles"] == 1 for g in groups),
          "a worker compiled more than one step program")
    check(len(res.events) >= 1, "no retune happened")
    got = np.asarray(states["g0"]["losses"], float)
    want = np.asarray(ref.get("losses", []), float)
    check(got.shape == want.shape and got.size > 0,
          f"group 0 ran {got.shape} steps, alone {want.shape}")
    check(np.all(np.isfinite(got)), f"non-finite loss in {got}")
    check(bool(np.all(got[:, 0] == want[:, 0])),
          "group 0 ran different batch sizes alone")
    check(bool(np.allclose(got[:, 1], want[:, 1], rtol=BF16_RTOL, atol=0)),
          f"group 0 losses {got[:, 1]} differ from alone {want[:, 1]}")
    check(abs(got[0, 1] - math.log(VOCAB)) <= LOSS_WINDOW,
          f"first loss {got[0, 1]:.4f} not within {LOSS_WINDOW} of "
          f"ln({VOCAB})")

    # every worker has exited: the chips are free for this process now
    dev, count = tpu_device()
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the one-process-per-chip runtime phase "
                         "on a four-chip host")
    args = ap.parse_args(argv)
    try:
        device = (four_chips if args.four_chips else one_chip)(args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
