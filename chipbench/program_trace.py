#!/usr/bin/env python3
"""What attaching the program's own tracer costs, in live tokens per
second.

    python3 chipbench/program_trace.py --workload <cell> --seed <n> \
        --seconds <s> [--pairs <k>] [--out <file.json>]

Set-up is the benchmark's own (``harness.set_up``). Then, on the same
trainer and compiled step, ``--pairs`` pairs of windows with the tracer
off and on (``jax.profiler.TraceAnnotation`` as its ``annotate``, no
profiler running), in the order off, on, on, off, ... The split of a
traced window by name scope and by the program's spans is the
benchmark's own ``--trace 1`` run (``harness.run``, ``trace_reduce``).
The last stdout line is one JSON object; ``--out`` writes it to a file
as well. Exits 2 with no result where JAX finds no TPU the benchmark
knows.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import statistics
import sys

if __name__ == "__main__":
    _ROOT = pathlib.Path(__file__).resolve().parents[1]
    sys.path[0] = str(_ROOT)
    sys.path.insert(1, str(_ROOT / "src"))


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
    from repro.obs import NULL_TRACER, Tracer

    cell = cells.load_cell(args.workload)
    try:
        dev = harness.require_chip(cell.chips)[0]
    except harness.NoChip as e:
        print(f"program_trace: {e}", file=sys.stderr, flush=True)
        return 2
    p = harness.set_up(cell, args.seed)
    seq = cell.traffic["seq_len"]

    def window(tracer):
        p.trainer.tracer = tracer
        steps, secs = harness.window(p, args.seconds)
        p.trainer.tracer = NULL_TRACER
        walls = [s.wall_s for s in steps]
        return {"tokens_per_s": _tokens_per_s(steps, secs, seq),
                "step_ms_p50": 1e3 * statistics.median(walls),
                "step_ms_max": 1e3 * max(walls), "steps": len(steps)}

    overhead = []
    for i in range(args.pairs):
        order = (False, True) if i % 2 == 0 else (True, False)
        for on in order:
            attached = (Tracer(source="train", annotate=TraceAnnotation)
                        if on else NULL_TRACER)
            overhead.append(dict(window(attached), tracer=on))

    out = {"cell": cell.name, "seed": args.seed,
           "device": {"platform": dev.platform, "kind": dev.device_kind},
           "overhead": overhead}
    line = json.dumps(out)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
