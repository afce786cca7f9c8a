"""Reduce a profiler trace (``.xplane.pb``) to what the metric readers read.

Read with ``jax.profiler.ProfileData`` and nothing else:

  window        from the start of the first to the end of the last host
                span named ``<prefix>step`` (the benchmark's own
                annotation around each timed call);
  busy          per device plane (``/device:TPU:<n>``), the union of the
                intervals of the operations on its ``XLA Ops`` line,
                clipped to the window; averaged over the devices;
  op_s          every operation's self time on the first device (an op
                nested in another, as a loop body's ops are in the loop,
                is taken out of its parent's time), summed by the HLO
                instruction's name; ``device_ops`` are the longest;
  op_names      each of those ops' ``op_name`` metadata, read from the
                compiled step's optimized HLO text where it is given:
                the name scopes it ran under (``attention``, ``mlp``,
                ...), a custom call's kernel name among them;
  span_s        seconds inside the window under each of the program's
                own host spans (``train.<phase>``, annotated through the
                program's tracer);
  idle_gaps     the longest stretches of the window in which the first
                device ran nothing, each labelled by the innermost
                benchmark span open on the host at its middle (``input``,
                ``control``, ``step`` for the rest of a timed call, and
                ``between`` outside any call);
  idle_by_span  the first device's idle time split, gap by gap, over the
                innermost span open on the host through it: a program
                span, ``step`` for the rest of a timed call, ``between``
                outside one.

``scope_ms`` and ``span_ms`` select from a reduction for the readers.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

Interval = Tuple[float, float]

OPS_LINE = "XLA Ops"
TOP = 10
PROGRAM_PREFIX = "train."


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def gaps(busy: List[Interval], lo: float, hi: float) -> List[Interval]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def _events(line):
    for ev in line.events:
        yield ev.name, float(ev.start_ns), float(ev.start_ns + ev.duration_ns)


def op_name(event_name: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def self_times(events, lo: float, hi: float) -> collections.Counter:
    """{op: time inside [lo, hi] not covered by ops nested in it}."""
    out: collections.Counter = collections.Counter()
    stack: List[Tuple[str, float]] = []
    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        while stack and stack[-1][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1][0]] -= e - s
        out[name] += e - s
        stack.append((name, e))
    return out


def read(path: str, prefixes: Tuple[str, ...]
         ) -> Tuple[Dict[str, List[Interval]],
                    Dict[str, List[Tuple[str, float, float]]]]:
    """({host span name: intervals}, {device plane: [(op, start, end)]}),
    in ns, for the host spans whose names start with one of
    ``prefixes``."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    spans: Dict[str, List[Interval]] = collections.defaultdict(list)
    devices: Dict[str, List[Tuple[str, float, float]]] = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for name, s, e in _events(line):
                    if name.startswith(prefixes):
                        spans[name].append((s, e))
        elif plane.name.startswith("/device:") and ":TPU:" in plane.name \
                and plane.name.split(":")[-1].isdigit():
            ops = [ln for ln in plane.lines if ln.name == OPS_LINE]
            devices[plane.name] = [(op_name(n), s, e) for ln in ops
                                   for n, s, e in _events(ln)]
    return spans, devices


class Timeline:
    """The innermost span open at each moment."""

    def __init__(self, spans: Dict[str, List[Interval]]):
        marks = []
        for label, ivs in spans.items():
            for s, e in ivs:
                marks.append((s, 1, e - s, label))
                marks.append((e, 0, e - s, label))
        marks.sort()
        self.starts: List[float] = []
        self.labels: List[str] = []
        active: List[Tuple[float, str]] = []
        for t, opening, width, label in marks:
            if opening:
                active.append((width, label))
            else:
                active.remove((width, label))
            self.starts.append(t)
            self.labels.append(min(active)[1] if active else "between")

    def at(self, t: float) -> str:
        i = bisect.bisect_right(self.starts, t) - 1
        return self.labels[i] if i >= 0 else "between"


def split_gap(timeline: Timeline, start: float, end: float) -> Dict[str, float]:
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


def idle_by_span(idle: Iterable[Interval], timeline: Timeline) -> Dict[str, float]:
    total: Dict[str, float] = collections.Counter()
    for s, e in idle:
        for label, d in split_gap(timeline, s, e).items():
            total[label] += d
    return dict(total)


# an HLO instruction's first line; the lines up to the next such line,
# or up to the end of its computation, belong to it (a Pallas custom
# call's kernel metadata spans lines)
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"\bmetadata=\{[^}]*?\bop_name=\"([^\"]*)\"")
_WRAPPED = re.compile(r"^(?:[\w.]+\()+|\)+$")


def _instructions(hlo_text: str) -> Iterable[Tuple[str, str]]:
    """(name, text with its continuation lines joined) of every
    instruction of every computation of an HLO module's text."""
    name, parts = None, []
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m or line.strip() in ("", "}"):
            if name is not None:
                yield name, "".join(parts)
            name, parts = (m.group(1), [line]) if m else (None, [])
        elif name is not None:
            parts.append(line)
    if name is not None:
        yield name, "".join(parts)


def hlo_op_names(hlo_text: str) -> Dict[str, str]:
    """{instruction name: its ``op_name`` metadata}, for every
    instruction of every computation (loop bodies included) of an HLO
    module's text, instructions that span lines included."""
    out = {}
    for name, text in _instructions(hlo_text):
        m = _OP_NAME.search(text)
        if m:
            out[name] = m.group(1)
    return out


def scopes_of(name: str) -> set:
    """The components of an ``op_name``, a component ``f(g(x))``
    counting as ``x``: ``jit(step)/transpose(jvp(mlp))/dot_general`` is
    in ``step``, ``mlp`` and ``dot_general``."""
    return {_WRAPPED.sub("", part) for part in name.split("/")}


def reduce(path: str, prefix: str, hlo_text: str = "") -> Dict:
    """The window of a profiler trace (its ``<prefix>step`` spans),
    with every op's self time named from ``hlo_text`` and the program's
    own spans (``PROGRAM_PREFIX``)."""
    spans, devices = read(path, (prefix, PROGRAM_PREFIX))
    steps = sorted(spans.get(prefix + "step", []))
    if not steps:
        raise ValueError(f"no {prefix}step spans in {path}")
    lo, hi = steps[0][0], max(e for _, e in steps)
    busy_per_device, op_time = [], collections.Counter()
    first_busy: List[Interval] = []
    for i, (_, events) in enumerate(sorted(devices.items())):
        u = union(clip([(s, e) for _, s, e in events], lo, hi))
        busy_per_device.append(sum(e - s for s, e in u))
        if i == 0:
            first_busy = u
            op_time = self_times(events, lo, hi)
    busy = (sum(busy_per_device) / len(busy_per_device)
            if busy_per_device else 0.0)
    idle = gaps(first_busy, lo, hi) if devices else []
    longest = sorted(idle, key=lambda g: g[0] - g[1])
    bench = {k[len(prefix):]: v for k, v in spans.items() if k.startswith(prefix)}
    program = {k: v for k, v in spans.items() if k.startswith(PROGRAM_PREFIX)}
    timeline = Timeline(bench)
    labelled = [(timeline.at((s + e) / 2), e - s) for s, e in longest]
    by_label = collections.Counter()
    for label, d in labelled:
        by_label[label] += d * 1e-9
    by_span = idle_by_span(idle, Timeline(dict(program, step=steps)))
    names = hlo_op_names(hlo_text)
    return {
        "busy_s": busy * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "n_steps": len(steps),
        "devices": len(devices),
        "device_ops": [[n, t * 1e-9] for n, t in op_time.most_common(TOP)],
        "idle_gaps": [[label, d * 1e-9] for label, d in labelled[:TOP]],
        "idle_by_label": dict(by_label),
        "op_s": {n: t * 1e-9 for n, t in op_time.items()},
        "op_names": {n: names[n] for n in op_time if n in names},
        "span_s": {k: sum(e - s for s, e in clip(v, lo, hi)) * 1e-9
                   for k, v in sorted(program.items())},
        "idle_by_span": {k: v * 1e-9 for k, v in sorted(by_span.items())},
    }


def scope_ms(trace: Optional[Dict], scope: str) -> Optional[float]:
    """Device self ms per step of the ops run under ``scope`` (a
    component of their ``op_name``); None where the trace names no op
    or none ran under it."""
    if not trace or not trace.get("op_names"):
        return None
    names = trace["op_names"]
    times = [t for op, t in trace["op_s"].items()
             if scope in scopes_of(names.get(op, ""))]
    return 1e3 * sum(times) / trace["n_steps"] if times else None


def span_ms(trace: Optional[Dict], span: str) -> Optional[float]:
    """Host ms per step under the program's span ``span``; None where
    the trace holds none."""
    if not trace or span not in trace.get("span_s", {}):
        return None
    return 1e3 * trace["span_s"][span] / trace["n_steps"]
