"""One run of one cell: set-up, the measured window, the check.

Set-up builds one object, the program's ``HeteroTrainer`` with its
compiled step and its state, from the benchmark's weights and the cell's
fixed speed curve (``from_probe``: no probe runs, so the plan's capacity
and the compiled shape are the cell's own). It drives that trainer
through the traffic's warm-up steps with the window's own call,
``trainer.run(steps=1, report_fn=...)``, recording the rows each step
trained on, and hands the same trainer to the window. The window times
every such call on the host clock; each call covers input, host-to-device
copies, dispatch, the loss sync, reports and the control poll. After the
window the program's state is freed and the reference follows the
warm-up steps (``compare.py``).
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import json
import math
import shutil
import sys
import tempfile
import time
from typing import Any, Callable, Dict, List, Optional

import jax
import numpy as np
from jax.profiler import TraceAnnotation

from chipbench import cells, compare, trace_reduce, traffic, weights

PEAKS = cells.HERE / "peaks.json"
SPAN = "chipbench."


class NoChip(RuntimeError):
    """JAX finds no accelerator the benchmark can measure."""


def peaks_for(kind: str) -> Dict[str, Any]:
    with open(PEAKS) as f:
        table = json.load(f)
    if kind not in table:
        raise NoChip(f"device kind {kind!r} is not in {PEAKS.name} "
                     f"({', '.join(table)})")
    return table[kind]


def require_chip(chips: int) -> List[Any]:
    """The devices the cell runs on; NoChip unless they are TPUs whose
    kind has known peaks."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX reports platform {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips; JAX reports {len(devs)}")
    peaks_for(devs[0].device_kind)
    return devs[:chips]


def compile_clock() -> Dict[str, float]:
    """XLA backend compiles or cache loads (count, seconds) and
    persistent-cache hits, as JAX's monitoring events report them."""
    from jax import monitoring

    totals = {"compiles": 0, "compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            totals["compiles"] += 1
            totals["compile_s"] += duration

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            totals["cache_hits"] += 1

    monitoring.register_event_duration_secs_listener(on_duration)
    monitoring.register_event_listener(on_event)
    return totals


# ``model`` keys that name a program attribute of another name
_ALIASES = {"head_dim": "resolved_head_dim", "compute_dtype": "dtype"}
# ``model`` keys of the benchmark's own: the program is held to them in
# ``set_up``
_OWN = ("param_dtype", "init_std")
# program fields that add a mechanism when set, as a non-None block does
_SWITCHES = ("cross_attn_every", "hybrid_attn_every", "is_encoder_decoder")


def _departures(obj, stated: Dict[str, Any], path: str = "") -> Dict[str, tuple]:
    """{key: (program, file)} for each key ``stated`` gives that ``obj``
    lacks or departs from, a nested object held field by field against
    the dataclass the attribute holds, and for each mechanism ``obj``
    has that ``stated`` does not state."""
    top = not path
    wrong: Dict[str, tuple] = {}
    for key, want in stated.items():
        if top and key in _OWN:
            continue
        attr = _ALIASES.get(key, key) if top else key
        where = path + key
        if not hasattr(obj, attr):
            wrong[where] = ("no such field", want)
            continue
        have = getattr(obj, attr)
        if isinstance(want, dict):
            if dataclasses.is_dataclass(have):
                wrong.update(_departures(have, want, where + "."))
            else:
                wrong[where] = (have, want)
        elif have != (tuple(want) if isinstance(want, list) else want):
            wrong[where] = (have, want)
    for f in dataclasses.fields(obj):
        have = getattr(obj, f.name)
        mechanism = dataclasses.is_dataclass(have) or (
            top and f.name in _SWITCHES and have)
        if mechanism and f.name not in stated:
            wrong[path + f.name] = (have, "not stated")
    return wrong


def _overridden(obj, overrides: Dict[str, Any]):
    """``obj`` with ``overrides``; an object value replaces fields of
    the block the attribute holds."""
    changes = {}
    for key, value in overrides.items():
        if isinstance(value, dict):
            block = getattr(obj, key, None)
            if not dataclasses.is_dataclass(block):
                raise ValueError(f"override {key!r}: the program holds "
                                 f"{block!r}, no block to change")
            value = _overridden(block, value)
        changes[key] = value
    return dataclasses.replace(obj, **changes)


def make_arch(config: Dict[str, Any]):
    """The registry's architecture with the file's overrides, held to
    every key of the file's ``model`` block and refused where it has a
    mechanism the block does not state."""
    from repro.configs.base import get_arch

    arch = _overridden(get_arch(config["arch"]), config["overrides"])
    wrong = _departures(arch, config["model"])
    if wrong:
        raise ValueError(f"the program's {config['arch']} departs from "
                         f"{config['name']}: {wrong} (program, file)")
    return arch


class Timers:
    """Host time per label, each call also a profiler annotation."""

    def __init__(self) -> None:
        self.t: Dict[str, float] = collections.defaultdict(float)

    def wrap(self, label: str, fn: Callable) -> Callable:
        name = SPAN + label

        def timed(*a, **k):
            t0 = time.perf_counter()
            with TraceAnnotation(name):
                try:
                    return fn(*a, **k)
                finally:
                    self.t[label] += time.perf_counter() - t0
        return timed

    def take(self) -> Dict[str, float]:
        out = dict(self.t)
        self.t.clear()
        return out


@dataclasses.dataclass
class Step:
    wall_s: float
    live_rows: int
    capacity_rows: int
    input_s: float
    control_s: float
    loss: float


@dataclasses.dataclass
class Program:
    """What set-up builds and hands to the window."""

    trainer: Any
    report_fn: Callable
    timers: Timers
    init_params: Callable
    key: np.ndarray
    batches: List[Dict[str, np.ndarray]]
    readings: Dict[str, Any]


def one_step(p: Program) -> Step:
    plan = p.trainer.control_plane.plan
    t0 = time.perf_counter()
    with TraceAnnotation(SPAN + "step"):
        p.trainer.run(steps=1, report_fn=p.report_fn)
    wall = time.perf_counter() - t0
    t = p.timers.take()
    return Step(wall, plan.global_batch, plan.global_capacity,
                t.get("input", 0.0), t.get("control", 0.0),
                p.trainer.records[-1].loss)


def set_up(cell: cells.Cell, seed: int, step_factory=None) -> Program:
    """Build the trainer and drive it through the warm-up steps.
    ``step_factory`` replaces the program's step builder (tests plant
    faults through it)."""
    from repro.core import hetero_dp
    from repro.core.speed_model import SpeedModel
    from repro.launch.train import HeteroTrainer, TrainerConfig
    from repro.models.model_factory import build_model
    from repro.optim.optimizer import AdamW, OptConfig

    m, mix = cell.config["model"], cell.traffic
    arch = make_arch(cell.config)
    shapes = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    dtypes = {str(s.dtype) for s in jax.tree.leaves(shapes)}
    if dtypes != {m["param_dtype"]}:
        raise ValueError(f"program parameters are {dtypes}, the file "
                         f"states {m['param_dtype']}")
    opt_cfg = OptConfig(**cell.config["optimizer"])
    init_params = weights.initializer(shapes, m["init_std"])
    key = np.asarray(weights.seed_key(seed))
    params = init_params(key)
    opt_state = jax.jit(AdamW(opt_cfg).init)(params)
    tcfg = TrainerConfig(seq_len=mix["seq_len"],
                         dataset_size=mix["dataset_size"], seed=seed,
                         log_every=0, opt=opt_cfg)
    if step_factory is not None:
        real = hetero_dp.make_train_step
        hetero_dp.make_train_step = step_factory(real)
    try:
        trainer = HeteroTrainer.from_probe(
            arch, traffic.groups(mix, SpeedModel), tcfg,
            state=(params, opt_state))
    finally:
        if step_factory is not None:
            hetero_dp.make_train_step = real
    del params, opt_state

    timers = Timers()
    batches: List[Dict[str, np.ndarray]] = []
    next_batch = trainer.pipeline.next_batch

    def recorded():
        b = next_batch()
        batches.append({k: np.array(b[k]) for k in
                        ("tokens", "targets", "sample_mask")})
        return b

    trainer.pipeline.next_batch = timers.wrap("input", recorded)
    cp = trainer.control_plane
    cp.poll = timers.wrap("control", cp.poll)
    cp.bus.publish = timers.wrap("control", cp.bus.publish)
    prog = Program(trainer, timers.wrap("control", traffic.report_fn(mix)),
                   timers, init_params, key, batches, {})

    b1 = opt_cfg.b1
    losses, retunes, n = [], [], 0
    while not traffic.warmup_done(mix, n, retunes):
        events = len(cp.events)
        losses.append(one_step(prog).loss)
        if len(cp.events) > events:
            retunes.append(n)
        if n == 0:
            prog.readings["grad_norms"] = {
                k: v / (1 - b1) for k, v in
                compare.leaf_norms(trainer.opt_state.mu).items()}
        n += 1
    prog.readings["losses"] = losses
    prog.readings["change_norms"] = compare.change_norms(
        trainer.params, init_params(key))
    prog.readings["retunes"] = retunes
    trainer.pipeline.next_batch = timers.wrap("input", next_batch)
    return prog


def distinct_rows(batches: List[Dict[str, np.ndarray]]) -> bool:
    rows = [b["tokens"][i].tobytes() for b in batches
            for i in np.flatnonzero(b["sample_mask"] > 0)]
    return len(set(rows)) == len(rows)


def window(p: Program, seconds: float) -> tuple:
    steps: List[Step] = []
    t0 = time.perf_counter()
    while True:
        steps.append(one_step(p))
        if time.perf_counter() - t0 >= seconds:
            break
    return steps, time.perf_counter() - t0


def free_program_state(p: Program) -> None:
    """Drop every array the program holds: the reference runs after."""
    p.trainer = None
    gc.collect()
    for a in jax.live_arrays():
        a.delete()


def check(cell: cells.Cell, p: Program) -> tuple:
    """(correct, checks, details) of the program's set-up steps against
    the reference."""
    ref = cells.module(cell.config["reference"]).run(
        cell.config["model"], cell.config["optimizer"],
        lambda: p.init_params(p.key), p.batches)
    values = compare.readings(p.readings, ref)
    ok, checks = compare.judge(values, cell.limits)
    ok = ok and distinct_rows(p.batches)
    return ok, checks, {k: v[1] for k, v in values.items()}


@dataclasses.dataclass
class Context:
    """What a metric reader reads (``metrics/<name>.py``). ``work`` is
    the module the configuration names under ``work``:
    ``train_flops_per_token(model, seq_len)`` and ``step_bytes(model)``
    count the work a step of this model requires."""

    steps: List[Step]
    window_s: float
    setup_s: float
    seq_len: int
    model: Dict[str, Any]
    work: Any
    peak: Dict[str, Any]
    chips: int
    memory_peak_bytes: int = 0
    trace: Optional[Dict[str, Any]] = None

    def flops_per_token(self) -> float:
        return self.work.train_flops_per_token(self.model, self.seq_len)

    def least_step_seconds(self, live_tokens: float) -> tuple:
        """(seconds, bound): the larger of FLOPs over peak and bytes over
        HBM bandwidth for one step on ``live_tokens``, and which of the
        two it is."""
        t_flops = live_tokens * self.flops_per_token() / self.peak["bf16_flops_per_s"]
        t_bytes = self.work.step_bytes(self.model) / self.peak["hbm_bytes_per_s"]
        return (t_flops, "compute") if t_flops >= t_bytes else (t_bytes, "memory")


def read_metrics(metrics: List[Dict[str, Any]], ctx: Context) -> Dict[str, Any]:
    """{name: {value, unit}} of each metric whose reader finds something
    to read."""
    out = {}
    for m in metrics:
        v = cells.reader(m["name"])(ctx)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def step_hlo(p: Program) -> str:
    """The optimized HLO text of the compiled step the window ran (from
    the persistent cache)."""
    t = p.trainer
    batch = {k: jax.numpy.asarray(v) for k, v in p.batches[-1].items()}
    return t.step_fn.lower(t.params, t.opt_state, batch).compile().as_text()


def run(name: str, seed: int, seconds: float, trace: bool, t0: float,
        devices: Optional[List[Any]] = None, step_factory=None,
        cell: Optional[cells.Cell] = None) -> Dict[str, Any]:
    """One run; returns the result line's object. ``devices``, ``cell``
    and ``step_factory`` let tests drive a run without the chip."""
    cell = cell or cells.load_cell(name)
    devices = devices if devices is not None else require_chip(cell.chips)
    dev = devices[0]
    peak = peaks_for(dev.device_kind) if dev.platform == "tpu" else \
        {"bf16_flops_per_s": math.nan, "hbm_bytes_per_s": math.nan}
    clock = compile_clock()
    log(f"cell {cell.name}: {dev.platform} {dev.device_kind} x{len(devices)}")

    p = set_up(cell, seed, step_factory)
    setup_s = time.perf_counter() - t0
    log(f"set-up {setup_s:.2f} s: {len(p.batches)} checked steps, retunes at "
        f"{p.readings['retunes']}, plan "
        f"{p.trainer.control_plane.plan.batch_sizes()}; programs compiled "
        f"or loaded {clock['compiles']} ({clock['compile_s']:.1f} s), of "
        f"them from the persistent cache {clock['cache_hits']}")

    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    if trace:
        # the program's own spans, annotated into the profiler's trace,
        # and its counters, for the traced window only
        from repro.obs import NULL_TRACER, MetricsRegistry, Tracer

        p.trainer.tracer = Tracer(source="train", annotate=TraceAnnotation)
        p.trainer.metrics = registry = MetricsRegistry()
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    before = dict(clock)
    steps, window_s = window(p, seconds)
    in_window = clock["compiles"] - before["compiles"] + \
        clock["cache_hits"] - before["cache_hits"]
    if trace:
        jax.profiler.stop_trace()
        p.trainer.tracer, p.trainer.metrics = NULL_TRACER, None
    programs = p.trainer.step_fn._cache_size()
    log(f"window {window_s:.3f} s: {len(steps)} steps; compiles in the "
        f"window {in_window}; step programs {programs}")
    if in_window or programs != 1:
        raise RuntimeError(f"{in_window} compile(s) inside the window, "
                           f"{programs} step program(s): the window must "
                           f"run one compiled step")

    stats = dev.memory_stats() or {}
    mem_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices) if stats else 0
    summary = None
    if trace:
        summary = trace_reduce.reduce(trace_reduce.find_xplane(tdir), SPAN,
                                      step_hlo(p))
        shutil.rmtree(tdir, ignore_errors=True)
        summary["counters"] = {k: v["value"] for k, v in
                               registry.snapshot().items() if "value" in v}
        log(f"trace: busy {summary['busy_s']:.4f} s of {summary['window_s']:.4f} s"
            f" over {summary['n_steps']} steps; idle by host span "
            f"{summary['idle_by_label']}; by program span "
            f"{summary['idle_by_span']}; ops named from the HLO "
            f"{len(summary['op_names'])} of {len(summary['op_s'])}; "
            f"counters {summary['counters']}")

    free_program_state(p)
    losses = [s.loss for s in steps]
    failed = sum(1 for x in losses if not math.isfinite(x))
    ok, checks, where = check(cell, p)
    ok = ok and failed == 0

    ctx = Context(steps, window_s, setup_s, cell.traffic["seq_len"],
                  cell.config["model"], cells.module(cell.config["work"]),
                  peak, len(devices), mem_peak, summary)
    metrics = read_metrics(cell.per_layer if trace else cell.end_to_end, ctx)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()), "memory_peak_bytes": mem_peak}
    result = {"correct": bool(ok), "attempted": len(steps), "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    log(f"flops per live token {ctx.flops_per_token():.6g}; least bytes per "
        f"step {ctx.work.step_bytes(ctx.model):.6g} ({cell.config['work']})")
    log(f"correct {ok}: rows distinct {distinct_rows(p.batches)}, "
        f"non-finite window losses {failed}, worst at {where}")
    for k, c in checks.items():
        log(f"{k} {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result
