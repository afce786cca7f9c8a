"""Trace-file tooling: ``python -m repro.launch.obs <cmd> TRACE``.

Reads the run timeline a ``--trace`` run wrote (Chrome trace-event JSON)
and either

  * ``summarize`` — span latency quantiles (p50/p99) per span kind and
    lane, every retune decision with its structured policy rationale,
    and the decision->effect lag histogram as ASCII bars; or
  * ``validate``  — the schema smoke check CI runs on trace artifacts:
    exits non-zero when the file is empty or malformed.

Both work on partial traces: a run killed mid-flight leaves only
complete events behind (DESIGN.md §14), so whatever is in the file
summarizes cleanly.
"""
from __future__ import annotations

import argparse
import sys
from collections import defaultdict
from typing import Dict, List

from repro.obs import load_trace, validate_events


def _quantile(sorted_vals: List[float], q: float) -> float:
    if not sorted_vals:
        return 0.0
    idx = min(int(q * len(sorted_vals)), len(sorted_vals) - 1)
    return sorted_vals[idx]


def _bars(counts: Dict, width: int = 30) -> List[str]:
    peak = max(counts.values(), default=0)
    out = []
    for key in sorted(counts):
        n = counts[key]
        bar = "#" * max(1, round(width * n / peak)) if peak else ""
        out.append(f"    {key!s:>8}  {n:>6}  {bar}")
    return out


def summarize(path: str) -> int:
    events = load_trace(path)
    if not events:
        print(f"{path}: empty trace", file=sys.stderr)
        return 1
    ts_lo = min(e["ts"] for e in events)
    ts_hi = max(e["ts"] + e.get("dur", 0.0) for e in events)
    lanes = sorted({e.get("src", "?") for e in events})
    print(f"trace: {path} — {len(events)} events, {len(lanes)} lanes "
          f"({', '.join(lanes)}), {ts_hi - ts_lo:.3f}s span")

    # span latencies per (cat/name), coordinator lanes and worker lanes
    # reported separately (worker step spans vary per group)
    spans: Dict[str, List[float]] = defaultdict(list)
    for e in events:
        if e.get("ph") != "X":
            continue
        src = e.get("src", "?")
        key = f"{e.get('cat', '?')}/{e['name']}"
        if src != "coord":
            key += f" [{src}]"
        spans[key].append(e.get("dur", 0.0))
    if spans:
        print("\nspan latencies (ms):")
        width = max(len(k) for k in spans)
        for key in sorted(spans):
            vals = sorted(spans[key])
            print(f"  {key:<{width}}  count={len(vals):>5}  "
                  f"p50={_quantile(vals, 0.50) * 1e3:>8.3f}  "
                  f"p99={_quantile(vals, 0.99) * 1e3:>8.3f}  "
                  f"max={vals[-1] * 1e3:>8.3f}")

    retunes = [e for e in events
               if e.get("cat") == "control" and e["name"] == "retune"]
    if retunes:
        print("\nretunes:")
        for e in retunes:
            a = e.get("args") or {}
            line = (f"  [round {a.get('step', '?')}] {a.get('group', '?')} "
                    f"{a.get('old_batch', '?')}->{a.get('new_batch', '?')} "
                    f"({a.get('reason', '?')})")
            why = []
            for k in ("policy", "rule", "silent_rounds"):
                if k in a:
                    why.append(f"{k}={a[k]}")
            for k in ("observed_speed", "required_speed"):
                if a.get(k) is not None:
                    why.append(f"{k.split('_')[0]}={a[k]:.1f}")
            if why:
                line += "  " + " ".join(why)
            print(line)

    lag_counts: Dict[int, int] = defaultdict(int)
    for e in events:
        if e["name"] == "retune_effect":
            lag_counts[int((e.get("args") or {}).get("lag_rounds", 0))] += 1
    if lag_counts:
        print("\nretune decision->effect lag (rounds):")
        print("\n".join(_bars(lag_counts)))

    faults = defaultdict(int)
    for e in events:
        if e.get("cat") == "fault":
            faults[e["name"]] += 1
    if faults:
        print("\nfault events:")
        print("\n".join(_bars(faults)))
    return 0


def validate(path: str) -> int:
    try:
        events = load_trace(path)
    except (OSError, ValueError) as e:
        print(f"{path}: unreadable trace: {e}", file=sys.stderr)
        return 1
    problems = validate_events(events)
    if problems:
        for p in problems:
            print(f"{path}: {p}", file=sys.stderr)
        return 1
    print(f"{path}: OK ({len(events)} events)")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.obs",
        description="Summarize or validate a run trace file.")
    sub = ap.add_subparsers(dest="cmd", required=True)
    for name in ("summarize", "validate"):
        p = sub.add_parser(name)
        p.add_argument("trace", help="trace file (Chrome trace-event JSON)")
    args = ap.parse_args(argv)
    return summarize(args.trace) if args.cmd == "summarize" \
        else validate(args.trace)


if __name__ == "__main__":
    raise SystemExit(main())
