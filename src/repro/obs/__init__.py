"""Run-wide observability plane (DESIGN.md §14).

Zero-dependency structured tracing + metrics for the Stannis stack:

  trace.py    ``Tracer`` — monotonic-clock spans/instants into a bounded
              ring buffer with pluggable sinks (in-memory, Chrome
              trace-event / Perfetto), plus the falsy ``NULL_TRACER``
              that makes every instrumentation site free when disabled,
              and ``gc_spans`` (garbage-collection pauses as spans);
  metrics.py  ``MetricsRegistry`` — counters, gauges and log-bucketed
              histograms (round latency, grant->report lag, frame/byte
              counts, shm hits, fault events);
  log.py      ``EventLog`` — the diagnostic print() replacement: human-
              readable lines to stderr, the same event to the trace sink.

The package imports nothing from the rest of ``repro`` (the runtime,
control plane and launch layers all import *it*), and nothing beyond
the stdlib — workers on any host can carry it.
"""
from repro.obs.log import LOG, EventLog
from repro.obs.metrics import Counter, Gauge, Histogram, MetricsRegistry
from repro.obs.trace import (NULL_TRACER, ChromeTraceSink, MemorySink,
                             NullTracer, TraceEvent, Tracer, chrome_trace,
                             gc_spans, load_trace, validate_events)

__all__ = [
    "LOG", "EventLog",
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_TRACER", "ChromeTraceSink", "MemorySink", "NullTracer",
    "TraceEvent", "Tracer", "chrome_trace", "gc_spans", "load_trace",
    "validate_events",
]
