#!/usr/bin/env python3
"""Run one cell with the program's own tracer attached, and split the
step by what the program was doing.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--pairs <k>] [--out <file.json>]

Set-up is the benchmark's own (``harness.set_up``). Then, on the same
trainer and compiled step:

  1. one window under the profiler with the tracer and a metrics
     registry attached (first, as the benchmark's traced window is),
     reduced here:

     h2d_ms, dispatch_ms, ...  mean host ms per window step in each
                               ``train`` span, from the tracer's ring;
     idle_by_span              the device's idle time split over the
                               innermost span open on the host through
                               each gap (``train.<phase>``; ``step`` for
                               the rest of a timed call, ``between``
                               outside one), gap by gap, not by its
                               midpoint;
     region_ms                 device self time per step under each
                               name scope of the step (``SCOPES``, or
                               ``other``), mapped from the step's
                               optimized HLO: each op goes to the first
                               scope found as a component of its
                               ``op_name`` (``transpose(jvp(mlp))``
                               counts as ``mlp``);

  2. ``--pairs`` pairs of windows, tracer off and tracer on (with
     ``jax.profiler.TraceAnnotation`` as its ``annotate``, no profiler
     running), in the order off, on, on, off, ...: what attaching the
     tracer costs in live tokens per second.

The HLO text comes from the same executable after the window
(``lower().compile()``, a persistent-cache hit). The last stdout line is
one JSON object; ``--out`` writes it to a file as well. Exits 2 with no
result where JAX finds no TPU the benchmark knows.
"""
from __future__ import annotations

import argparse
import bisect
import collections
import json
import os
import pathlib
import re
import shutil
import statistics
import sys
import tempfile
from typing import Dict, Iterable, Tuple

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[0] = str(_ROOT)
    sys.path.insert(1, str(_ROOT / "src"))

from chipbench import trace_reduce  # noqa: E402

SCOPES = ("attention", "mlp", "loss_head", "optimizer")
OTHER = "other"

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name=\"([^\"]*)\"", re.M)
_WRAPPED = re.compile(r"^(?:[\w.]+\()+|\)+$")


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its ``op_name`` metadata}, for every
    instruction of every computation (loop bodies included) of an HLO
    module's text."""
    return dict(_INSTRUCTION.findall(hlo_text))


def region_of(op_name: str) -> str:
    """The first of ``SCOPES`` that is a component of ``op_name``, a
    component ``f(g(x))`` counting as ``x``; ``other`` if none is."""
    for part in op_name.split("/"):
        core = _WRAPPED.sub("", part)
        if core in SCOPES:
            return core
    return OTHER


def region_seconds(op_seconds: Dict[str, float],
                   op_names: Dict[str, str]) -> Dict[str, float]:
    """Device self time summed by region; an op the HLO does not name
    is ``other``."""
    out = {r: 0.0 for r in SCOPES + (OTHER,)}
    for op, t in op_seconds.items():
        out[region_of(op_names.get(op, ""))] += t
    return out


def split_gap(timeline: trace_reduce.Timeline, start: float,
              end: float) -> Dict[str, float]:
    """{label: the part of [start, end] during which it was the
    innermost open span}."""
    out: Dict[str, float] = collections.Counter()
    i = bisect.bisect_right(timeline.starts, start) - 1
    t = start
    while t < end:
        label = timeline.labels[i] if i >= 0 else "between"
        nxt = timeline.starts[i + 1] if i + 1 < len(timeline.starts) else end
        piece_end = min(end, max(nxt, t))
        if piece_end > t:
            out[label] += piece_end - t
        t = piece_end
        i += 1
    return dict(out)


def idle_by_span(gaps: Iterable[Tuple[float, float]],
                 timeline: trace_reduce.Timeline) -> Dict[str, float]:
    total: Dict[str, float] = collections.Counter()
    for s, e in gaps:
        for label, d in split_gap(timeline, s, e).items():
            total[label] += d
    return dict(total)


def reduce_program(path: str, hlo_text: str, bench_prefix: str,
                   program_prefix: str = "train.") -> Dict:
    """The benchmark's window (its ``<bench_prefix>step`` spans) of a
    profiler trace, with the device's idle time split over the
    program's spans and its busy time over the step's name scopes."""
    steps_spans, devices = trace_reduce.read(path, bench_prefix)
    prog_spans, _ = trace_reduce.read(path, program_prefix)
    steps = sorted(steps_spans.get("step", []))
    if not steps:
        raise ValueError(f"no {bench_prefix}step spans in {path}")
    lo, hi = steps[0][0], max(e for _, e in steps)
    first = sorted(devices.items())[0][1] if devices else []
    busy = trace_reduce.union(trace_reduce.clip(
        [(s, e) for _, s, e in first], lo, hi))
    idle = trace_reduce.gaps(busy, lo, hi)
    labels = {"step": steps}
    labels.update({program_prefix + k: v for k, v in prog_spans.items()})
    timeline = trace_reduce.Timeline(labels)
    by_span = idle_by_span(idle, timeline)
    longest = sorted(idle, key=lambda g: g[0] - g[1])[:trace_reduce.TOP]
    op_time = trace_reduce.self_times(first, lo, hi)
    op_names = hlo_op_names(hlo_text)
    regions = region_seconds(op_time, op_names)
    others = [(op, t) for op, t in op_time.most_common()
              if region_of(op_names.get(op, "")) == OTHER][:trace_reduce.TOP]
    n = len(steps)
    busy_s = sum(e - s for s, e in busy) * 1e-9
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "n_steps": n,
        "idle_s": (hi - lo) * 1e-9 - busy_s,
        "idle_by_span": {k: v * 1e-9 for k, v in sorted(by_span.items())},
        "idle_gaps": [{"s": (e - s) * 1e-9,
                       "by_span": {k: v * 1e-9 for k, v in
                                   split_gap(timeline, s, e).items()}}
                      for s, e in longest],
        "region_ms": {k: v * 1e-6 / n for k, v in regions.items()},
        "other_ops": [[op, t * 1e-9] for op, t in others],
        "ops_named": sum(1 for op in op_time if op in op_names),
        "ops": len(op_time),
    }


def span_ms(events, names: Iterable[str], n_steps: int) -> Dict[str, float]:
    """Mean host ms per step in each named ``train`` span."""
    total: Dict[str, float] = collections.Counter()
    for ev in events:
        if ev.cat == "train" and ev.ph == "X":
            total[ev.name] += ev.dur
    return {k: 1e3 * total.get(k, 0.0) / n_steps for k in names}


def _tokens_per_s(steps, window_s: float, seq_len: int) -> float:
    return sum(s.live_rows for s in steps) * seq_len / window_s


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--pairs", type=int, default=2)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    from chipbench import run as bench_run

    bench_run.keep_logs_in_tmpdir()
    import jax
    from jax.profiler import TraceAnnotation

    bench_run.pin_cache(jax)
    from chipbench import cells, harness
    from repro.launch.train import STEP_SPANS
    from repro.obs import NULL_TRACER, MetricsRegistry, Tracer

    cell = cells.load_cell(args.workload)
    try:
        dev = harness.require_chip(cell.chips)[0]
    except harness.NoChip as e:
        print(f"program_trace: {e}", file=sys.stderr, flush=True)
        return 2
    clock = harness.compile_clock()
    p = harness.set_up(cell, args.seed)
    seq = cell.traffic["seq_len"]
    trainer = p.trainer

    def window(tracer):
        trainer.tracer = tracer
        steps, secs = harness.window(p, args.seconds)
        trainer.tracer = NULL_TRACER
        walls = [s.wall_s for s in steps]
        return {"tokens_per_s": _tokens_per_s(steps, secs, seq),
                "step_ms_p50": 1e3 * statistics.median(walls),
                "step_ms_max": 1e3 * max(walls), "steps": len(steps)}

    # the profiled window comes first, right after set-up, as the
    # benchmark's own traced window does
    tracer = Tracer(source="train", capacity=1 << 20,
                    annotate=TraceAnnotation)
    trainer.metrics = metrics = MetricsRegistry()
    tdir = tempfile.mkdtemp(prefix="program-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    before = clock["compiles"] + clock["cache_hits"]
    jax.profiler.start_trace(tdir, profiler_options=opts)
    trainer.tracer = tracer
    steps, window_s = harness.window(p, args.seconds)
    trainer.tracer, trainer.metrics = NULL_TRACER, None
    jax.profiler.stop_trace()
    in_window = clock["compiles"] + clock["cache_hits"] - before

    overhead = []
    for i in range(args.pairs):
        order = (False, True) if i % 2 == 0 else (True, False)
        for on in order:
            attached = (Tracer(source="train", annotate=TraceAnnotation)
                        if on else NULL_TRACER)
            overhead.append(dict(window(attached), tracer=on))

    # the cells are dense decoders: the batch has no modality inputs
    batch = {k: jax.numpy.asarray(v) for k, v in p.batches[-1].items()}
    hlo = trainer.step_fn.lower(trainer.params, trainer.opt_state,
                                batch).compile().as_text()
    out = reduce_program(trace_reduce.find_xplane(tdir), hlo, harness.SPAN)
    shutil.rmtree(tdir, ignore_errors=True)
    counters = {k: v.get("value") for k, v in metrics.snapshot().items()}
    out.update({
        "cell": cell.name, "seed": args.seed,
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "module": hlo.split(",", 1)[0].split()[-1],
        "traced_tokens_per_s": _tokens_per_s(steps, window_s, seq),
        "steps": len(steps),
        "span_ms": span_ms(tracer.events(),
                           STEP_SPANS + ("retune", "gc"), len(steps)),
        "gc_spans": sum(1 for e in tracer.events() if e.name == "gc"),
        "compile_instants": sum(1 for e in tracer.events()
                                if e.name == "compile"),
        "compiles_in_window": in_window,
        "counters": counters,
        "overhead": overhead,
    })
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
