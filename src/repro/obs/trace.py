"""Structured tracing: monotonic-clock spans and instants (DESIGN.md §14).

A :class:`Tracer` records :class:`TraceEvent`\\ s — instants (``ph="i"``)
and *complete* spans (``ph="X"``: start timestamp + duration, emitted
only when the span closes) — into a bounded ring buffer, fanning each
event out to pluggable sinks. Emitting only complete events is what
keeps a trace well-formed under faults: a SIGKILLed worker simply never
emits the span it was inside (there is no dangling "begin" to corrupt
the file), and a span that unwinds through an exception is emitted with
``aborted: true`` in its args.

Timestamps are ``time.perf_counter()`` seconds — monotonic, per
process. Worker events travel to the coordinator piggybacked on report
traffic (``messages.py`` ``obs`` fields) as compact wire lists;
:meth:`Tracer.ingest` re-stamps them onto the coordinator's clock with
a per-source offset anchored so a batch's newest event lands exactly at
the coordinator's receive time — every worker event therefore sorts
*before* the coordinator event that observed it (causal order), without
any cross-host clock agreement.

Disabled tracing must cost nothing: :data:`NULL_TRACER` is falsy, so
every hot instrumentation site guards with ``if tracer:`` — one branch,
zero allocations, zero calls. Sink exceptions are isolated (recorded on
``Tracer.sink_errors``, never raised into the instrumented loop).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import gc
import json
import time
from typing import Callable, ContextManager, Dict, Iterator, List, Optional

__all__ = ["TraceEvent", "Tracer", "NullTracer", "NULL_TRACER",
           "MemorySink", "ChromeTraceSink", "chrome_trace", "gc_spans",
           "load_trace", "validate_events"]

_PHASES = ("X", "i", "M")                # complete, instant, metadata


@dataclasses.dataclass
class TraceEvent:
    """One trace record. ``ts``/``dur`` are seconds on the emitting
    tracer's clock (re-stamped to the coordinator clock on ingest)."""

    ts: float
    cat: str
    name: str
    ph: str = "i"                        # "i" instant | "X" complete
    dur: float = 0.0                     # span duration (X only)
    src: str = "coord"                   # lane: coord or worker group
    args: Optional[Dict] = None

    def to_wire(self) -> List:
        """Compact wire list for report piggybacking; ``src`` is implied
        by the sending channel and re-attached on ingest."""
        return [self.ts, self.dur, self.cat, self.name, self.ph, self.args]

    @classmethod
    def from_wire(cls, values: List, src: str,
                  offset: float = 0.0) -> "TraceEvent":
        ts, dur, cat, name, ph, args = values
        return cls(float(ts) + offset, str(cat), str(name), str(ph),
                   float(dur), src, args)

    def to_json(self) -> Dict:
        out = {"ts": self.ts, "cat": self.cat, "name": self.name,
               "ph": self.ph, "src": self.src}
        if self.ph == "X":
            out["dur"] = self.dur
        if self.args is not None:
            out["args"] = self.args
        return out


class _Span:
    """Context manager emitting ONE complete event at close (or an
    ``aborted`` one when unwinding through an exception). With the
    tracer's ``annotate`` set, the span is also an annotation named
    ``<cat>.<name>`` for as long as it is open."""

    __slots__ = ("_tr", "cat", "name", "args", "t0", "_ann")

    def __init__(self, tracer: "Tracer", cat: str, name: str,
                 args: Optional[Dict]) -> None:
        self._tr = tracer
        self.cat = cat
        self.name = name
        self.args = args
        self.t0 = 0.0
        self._ann = None

    def __enter__(self) -> "_Span":
        annotate = self._tr.annotate
        if annotate is not None:
            self._ann = annotate(f"{self.cat}.{self.name}")
            self._ann.__enter__()
        self.t0 = self._tr.now()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = self._tr.now() - self.t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
            self._ann = None
        args = self.args
        if exc_type is not None:
            args = dict(args or ())
            args["aborted"] = True
        self._tr.complete(self.cat, self.name, self.t0, dur, args)
        return False


class Tracer:
    """Bounded-ring trace recorder with sink fan-out.

    ``capacity`` bounds the in-memory ring (``events()`` /
    ``drain_wire()`` read it); sinks see EVERY event regardless — the
    ring bounds memory, not the file. Worker-side tracers run ring-only
    (no sinks) and are drained by the piggyback path.

    ``annotate`` is a context-manager factory taking a name, entered
    around every span: ``jax.profiler.TraceAnnotation`` puts each span
    into a profiler trace as well, on the clock its device operations
    are aligned to."""

    enabled = True

    def __init__(self, source: str = "coord", capacity: int = 65536,
                 sinks: Optional[List] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 annotate: Optional[Callable[[str], ContextManager]] = None
                 ) -> None:
        self.source = source
        self.annotate = annotate
        self._clock = clock
        self._ring: collections.deque = collections.deque(maxlen=capacity)
        self._sinks = list(sinks or ())
        self.sink_errors: List[str] = []
        # per-ingest-source clock offset (seconds to ADD to foreign ts)
        self._offsets: Dict[str, float] = {}

    def __bool__(self) -> bool:
        return True

    def now(self) -> float:
        return self._clock()

    # -- emission -------------------------------------------------------
    def instant(self, cat: str, name: str,
                args: Optional[Dict] = None) -> None:
        self._emit(TraceEvent(self._clock(), cat, name, "i",
                              src=self.source, args=args))

    def complete(self, cat: str, name: str, ts: float, dur: float,
                 args: Optional[Dict] = None) -> None:
        self._emit(TraceEvent(ts, cat, name, "X", dur=dur,
                              src=self.source, args=args))

    def span(self, cat: str, name: str,
             args: Optional[Dict] = None) -> _Span:
        return _Span(self, cat, name, args)

    def _emit(self, ev: TraceEvent) -> None:
        self._ring.append(ev)
        for sink in self._sinks:
            try:
                sink.emit(ev)
            except Exception as e:       # a broken sink must never kill
                if len(self.sink_errors) < 64:    # the traced loop
                    self.sink_errors.append(
                        f"{type(sink).__name__}: {type(e).__name__}: {e}")

    # -- piggyback / merge ----------------------------------------------
    def drain_wire(self) -> List[List]:
        """Pop the ring as wire lists (the worker-side flush). Returns
        ``[]`` when nothing accumulated."""
        if not self._ring:
            return []
        out = [ev.to_wire() for ev in self._ring]
        self._ring.clear()
        return out

    def ingest(self, src: str, wire_events: List[List],
               recv_ts: Optional[float] = None) -> None:
        """Merge a foreign event batch onto THIS tracer's clock.

        The first batch from ``src`` anchors a constant offset mapping
        its newest event end to ``recv_ts`` (the coordinator-side
        receive time) — every event in every batch from that source
        then sorts before the coordinator event that observed it.
        ``src`` should name the worker *life* (``group#incarnation``):
        a restarted worker is a new process with a new clock epoch and
        gets a fresh anchor."""
        if not wire_events:
            return
        if recv_ts is None:
            recv_ts = self._clock()
        offset = self._offsets.get(src)
        if offset is None:
            ends = []
            for v in wire_events:
                try:
                    ends.append(float(v[0]) + float(v[1]))
                except (TypeError, ValueError, IndexError):
                    pass                 # the per-event loop reports it
            if not ends:
                self.instant("error", "bad_obs_event", {"src": src})
                return
            offset = self._offsets[src] = recv_ts - max(ends)
        for values in wire_events:
            try:
                self._emit(TraceEvent.from_wire(values, src, offset))
            except (TypeError, ValueError, IndexError):
                self.instant("error", "bad_obs_event", {"src": src})

    # -- readout --------------------------------------------------------
    def events(self) -> List[TraceEvent]:
        return list(self._ring)

    def close(self) -> None:
        for sink in self._sinks:
            try:
                sink.close()
            except Exception as e:
                if len(self.sink_errors) < 64:
                    self.sink_errors.append(
                        f"{type(sink).__name__}: {type(e).__name__}: {e}")


class NullTracer:
    """The disabled tracer: falsy, every operation a no-op. Hot sites
    guard with ``if tracer:`` so the disabled path is one branch."""

    enabled = False
    source = "null"
    sink_errors: List[str] = []

    def __bool__(self) -> bool:
        return False

    def now(self) -> float:
        return 0.0

    def instant(self, cat, name, args=None) -> None:
        pass

    def complete(self, cat, name, ts, dur, args=None) -> None:
        pass

    def span(self, cat, name, args=None) -> "NullTracer":
        return self

    def __enter__(self) -> "NullTracer":
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        return False

    def drain_wire(self) -> List:
        return []

    def ingest(self, src, wire_events, recv_ts=None) -> None:
        pass

    def events(self) -> List:
        return []

    def close(self) -> None:
        pass


NULL_TRACER = NullTracer()


# -- sinks ------------------------------------------------------------------


class MemorySink:
    """Keep every event in a list — the test sink."""

    def __init__(self) -> None:
        self.events: List[TraceEvent] = []
        self.closed = False

    def emit(self, ev: TraceEvent) -> None:
        self.events.append(ev)

    def close(self) -> None:
        self.closed = True


class ChromeTraceSink:
    """Accumulate events and write one Chrome trace-event JSON object
    (``{"traceEvents": [...]}``) at close — loadable in Perfetto
    (https://ui.perfetto.dev) and ``chrome://tracing``."""

    def __init__(self, path: str) -> None:
        self.path = path
        self._events: List[TraceEvent] = []
        self._written = False

    def emit(self, ev: TraceEvent) -> None:
        self._events.append(ev)

    def close(self) -> None:
        if self._written:
            return
        self._written = True
        with open(self.path, "w") as f:
            json.dump(chrome_trace(self._events), f,
                      separators=(",", ":"))


def chrome_trace(events: List[TraceEvent]) -> Dict:
    """Chrome trace-event JSON from a merged event list: one pid, one
    tid (lane) per source, timestamps rebased to µs from the earliest
    event, sorted by time — the causally-ordered run timeline."""
    if not events:
        return {"traceEvents": [], "displayTimeUnit": "ms"}
    t0 = min(ev.ts for ev in events)
    lanes: Dict[str, int] = {}
    out: List[Dict] = []
    for ev in sorted(events, key=lambda e: (e.ts, e.dur)):
        tid = lanes.setdefault(ev.src, len(lanes) + 1)
        rec = {"name": ev.name, "cat": ev.cat, "ph": ev.ph,
               "ts": round((ev.ts - t0) * 1e6, 3), "pid": 1, "tid": tid}
        if ev.ph == "X":
            rec["dur"] = round(ev.dur * 1e6, 3)
        elif ev.ph == "i":
            rec["s"] = "t"               # thread-scoped instant
        if ev.args is not None:
            rec["args"] = ev.args
        out.append(rec)
    meta = [{"name": "thread_name", "ph": "M", "pid": 1, "tid": tid,
             "args": {"name": src}} for src, tid in lanes.items()]
    return {"traceEvents": meta + out, "displayTimeUnit": "ms"}


@contextlib.contextmanager
def gc_spans(tracer: "Tracer", cat: str) -> Iterator[None]:
    """Inside the block, every Python garbage collection becomes a
    ``<cat>/gc`` span (args: generation, objects collected)."""
    open_spans: List[_Span] = []

    def hook(phase: str, info: Dict) -> None:
        if phase == "start":
            span = tracer.span(cat, "gc", {"generation": info["generation"]})
            span.__enter__()
            open_spans.append(span)
        elif open_spans:
            span = open_spans.pop()
            span.args["collected"] = info["collected"]
            span.__exit__(None, None, None)

    gc.callbacks.append(hook)
    try:
        yield
    finally:
        gc.callbacks.remove(hook)


# -- trace-file readers (the summarize/validate CLI and CI smoke) -----------


def load_trace(path: str) -> List[Dict]:
    """Read a Chrome trace-event file (``{"traceEvents": [...]}``, what
    :class:`ChromeTraceSink` writes) into a list of event dicts with
    ``ts``/``dur`` normalized to SECONDS and ``src`` resolved to the lane
    name. Raises ValueError for anything else."""
    with open(path) as f:
        doc = json.load(f)
    if not (isinstance(doc, dict) and "traceEvents" in doc):
        raise ValueError(f"{path}: not a Chrome trace-event file")
    raw = doc["traceEvents"]
    names = {ev.get("tid"): (ev.get("args") or {}).get("name")
             for ev in raw if ev.get("ph") == "M"
             and ev.get("name") == "thread_name"}
    out = []
    for ev in raw:
        if ev.get("ph") == "M":
            continue
        out.append({
            "ts": float(ev.get("ts", 0.0)) / 1e6,
            "dur": float(ev.get("dur", 0.0)) / 1e6,
            "cat": ev.get("cat", ""), "name": ev.get("name", ""),
            "ph": ev.get("ph", "i"),
            "src": names.get(ev.get("tid"), str(ev.get("tid", "?"))),
            "args": ev.get("args"),
        })
    return out


def validate_events(events: List) -> List[str]:
    """Schema check over loaded events (the CI smoke): every event has
    a name, a known phase, finite non-negative timestamps, and spans a
    finite non-negative duration. Accepts loaded dicts or live
    :class:`TraceEvent` objects. Returns problems (empty = valid)."""
    problems: List[str] = []
    if not events:
        problems.append("trace contains no events")
    for i, ev in enumerate(events[:100000]):
        if isinstance(ev, TraceEvent):
            ev = ev.to_json()
        where = f"event {i} ({ev.get('name', '?')!r})"
        if not ev.get("name"):
            problems.append(f"{where}: missing name")
        if ev.get("ph") not in _PHASES:
            problems.append(f"{where}: unknown phase {ev.get('ph')!r}")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts != ts or ts < 0:
            problems.append(f"{where}: bad ts {ts!r}")
        if ev.get("ph") == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur != dur or dur < 0:
                problems.append(f"{where}: span with bad dur {dur!r}")
        args = ev.get("args")
        if args is not None and not isinstance(args, dict):
            problems.append(f"{where}: args is not an object")
        if len(problems) >= 50:
            problems.append("... (truncated)")
            break
    return problems
