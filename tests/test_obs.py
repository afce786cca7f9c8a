"""The observability plane (ISSUE 7): tracing, metrics, merged timelines.

Acceptance anchors:
  * the Tracer emits only COMPLETE spans (well-formed under faults),
    bounds its ring, and isolates sink failures from the traced loop;
  * NULL_TRACER is falsy and free: every hot site guards with
    ``if tracer:`` so the disabled path is one branch — proven by the
    Fig. 6 exact-parity gates holding traced AND untraced (k=0 and 2);
  * worker event batches piggyback on report traffic (``obs`` wire
    fields, omitted at default so legacy shapes are pinned) and merge
    into one causally-ordered coordinator timeline;
  * every retune lands in the trace as a structured event carrying its
    policy rationale (which rule fired, observed vs required speed);
  * TelemetryBus.publish isolates subscriber exceptions (a broken
    observer can never take down the round or starve later observers);
  * StepBuckets reports its depth through an optional hook only — no
    observability cost when unwired;
  * SIGKILL / SIGSTOP fault runs through ProcessManager still produce
    schema-valid traces with the fault instants recorded.
"""
from __future__ import annotations

import json

import pytest

from repro.core.control.telemetry import StepBuckets, StepReport, TelemetryBus
from repro.obs import (NULL_TRACER, ChromeTraceSink, Counter, Gauge,
                       Histogram, MemorySink, MetricsRegistry, NullTracer,
                       TraceEvent, Tracer, chrome_trace, gc_spans,
                       load_trace, validate_events)
from repro.runtime.ipc import CODECS
from repro.runtime.messages import (CheckpointAck, Message, ReportBatch,
                                    StepReportMsg)
from repro.runtime.parity import dropout_parity, fig6_parity


# ---------------------------------------------------------------------------
# Tracer core
# ---------------------------------------------------------------------------


class TestTracer:
    def test_span_emits_one_complete_event(self):
        tr = Tracer(source="coord")
        with tr.span("round", "collect", {"step": 3}):
            pass
        (ev,) = tr.events()
        assert (ev.ph, ev.cat, ev.name) == ("X", "round", "collect")
        assert ev.args == {"step": 3}
        assert ev.dur >= 0.0

    def test_span_unwinding_through_exception_marks_aborted(self):
        tr = Tracer()
        with pytest.raises(RuntimeError):
            with tr.span("worker", "step"):
                raise RuntimeError("boom")
        (ev,) = tr.events()
        assert ev.ph == "X" and ev.args == {"aborted": True}

    def test_ring_is_bounded_but_sinks_see_everything(self):
        sink = MemorySink()
        tr = Tracer(capacity=4, sinks=[sink])
        for i in range(10):
            tr.instant("t", f"e{i}")
        assert len(tr.events()) == 4
        assert [e.name for e in tr.events()] == ["e6", "e7", "e8", "e9"]
        assert len(sink.events) == 10

    def test_sink_exception_is_isolated_and_bounded(self):
        class BrokenSink:
            def emit(self, ev):
                raise OSError("disk full")

            def close(self):
                raise OSError("still full")

        tr = Tracer(sinks=[BrokenSink(), MemorySink()])
        for _ in range(100):
            tr.instant("t", "e")
        tr.close()
        assert len(tr.events()) == 100          # the loop never saw it
        assert tr.sink_errors and len(tr.sink_errors) <= 64
        assert "OSError" in tr.sink_errors[0]

    def test_null_tracer_is_falsy_and_free(self):
        assert not NULL_TRACER
        assert isinstance(NULL_TRACER, NullTracer)
        with NULL_TRACER.span("a", "b"):
            pass
        NULL_TRACER.instant("a", "b")
        assert NULL_TRACER.drain_wire() == []
        assert NULL_TRACER.events() == []

    def test_annotate_opens_around_each_span(self):
        log = []

        class Ann:
            def __init__(self, name):
                self.name = name

            def __enter__(self):
                log.append(("in", self.name))

            def __exit__(self, *exc):
                log.append(("out", self.name, exc[0]))

        tr = Tracer(annotate=Ann)
        with tr.span("train", "dispatch"):
            assert log == [("in", "train.dispatch")]
        with pytest.raises(RuntimeError):
            with tr.span("train", "sync"):
                raise RuntimeError("boom")
        tr.instant("train", "compile")           # instants annotate nothing
        assert log[1:] == [("out", "train.dispatch", None),
                           ("in", "train.sync"),
                           ("out", "train.sync", RuntimeError)]
        assert [e.name for e in tr.events()] == ["dispatch", "sync",
                                                 "compile"]

    def test_gc_spans_only_inside_the_block(self):
        import gc

        tr = Tracer()
        with gc_spans(tr, "train"):
            gc.collect()
        inside = len(tr.events())
        gc.collect()
        assert len(tr.events()) == inside >= 1
        ev = tr.events()[-1]
        assert (ev.ph, ev.cat, ev.name) == ("X", "train", "gc")
        assert ev.args["generation"] == 2 and ev.args["collected"] >= 0

    def test_drain_wire_pops_the_ring(self):
        tr = Tracer(source="xeon0")
        tr.instant("worker", "throttled", {"cap": 0.5})
        wire = tr.drain_wire()
        assert len(wire) == 1 and tr.events() == []
        assert tr.drain_wire() == []
        ev = TraceEvent.from_wire(wire[0], src="xeon0#0")
        assert (ev.cat, ev.name, ev.src) == ("worker", "throttled", "xeon0#0")
        assert ev.args == {"cap": 0.5}


class TestIngestMerge:
    def test_ingest_anchors_foreign_clock_at_receive_time(self):
        """A worker on a clock 1000s ahead: after ingest its newest
        event ends exactly at the coordinator's receive timestamp and
        every worker event sorts BEFORE the coordinator event that
        observed the batch (causal order without clock agreement)."""
        worker = Tracer(source="xeon1", clock=lambda: 1000.0)
        worker.complete("worker", "step", 999.0, 0.5)
        worker.instant("worker", "throttled")
        coord = Tracer(source="coord")
        recv = coord.now()
        coord.ingest("xeon1#0", worker.drain_wire(), recv_ts=recv)
        coord.instant("round", "collected")
        evs = coord.events()
        newest = max(e.ts + e.dur for e in evs if e.src == "xeon1#0")
        assert newest == pytest.approx(recv, abs=1e-9)
        assert all(e.ts + e.dur <= evs[-1].ts for e in evs[:-1])

    def test_ingest_offset_is_stable_per_source(self):
        worker = Tracer(source="g", clock=lambda: 50.0)
        coord = Tracer()
        worker.complete("w", "a", 49.0, 1.0)
        coord.ingest("g#0", worker.drain_wire(), recv_ts=100.0)
        worker.complete("w", "b", 51.0, 1.0)
        coord.ingest("g#0", worker.drain_wire(), recv_ts=999.0)
        a, b = coord.events()
        # same anchor: b lands 2s after a on the coordinator clock, NOT
        # re-anchored to the second receive time
        assert b.ts - a.ts == pytest.approx(2.0)

    def test_ingest_bad_event_becomes_error_instant(self):
        coord = Tracer()
        coord.ingest("g#0", [["not-a-ts", None]], recv_ts=None)
        names = [(e.cat, e.name) for e in coord.events()]
        assert ("error", "bad_obs_event") in names


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------


class TestMetrics:
    def test_histogram_quantiles_within_bucket_error(self):
        h = Histogram()
        for v in range(1, 1001):
            h.record(float(v))
        assert h.count == 1000 and h.mean == pytest.approx(500.5)
        # log buckets: ~±9% relative error per bucket
        assert h.quantile(0.50) == pytest.approx(500.0, rel=0.15)
        assert h.quantile(0.99) == pytest.approx(990.0, rel=0.15)
        assert h.quantile(0.0) == 1.0 and h.quantile(1.0) == 1000.0

    def test_histogram_zero_and_negative_underflow(self):
        h = Histogram()
        h.record(0.0)
        h.record(-3.0)
        h.record(8.0)
        assert h.zero == 2 and h.count == 3
        assert h.quantile(0.3) <= 0.0

    def test_registry_get_or_create_and_type_guard(self):
        mx = MetricsRegistry()
        c = mx.counter("coord.reports")
        c.inc(3)
        assert mx.counter("coord.reports") is c and c.value == 3
        assert isinstance(mx.gauge("g"), Gauge)
        assert isinstance(mx.counter("c2"), Counter)
        with pytest.raises(TypeError):
            mx.histogram("coord.reports")
        assert mx.get("nope") is None
        assert "coord.reports" in mx.names()

    def test_summary_line_reads_headline_metrics(self):
        mx = MetricsRegistry()
        mx.histogram("coord.round_latency_s").record(0.002)
        mx.counter("coord.reports").inc(42)
        line = mx.summary_line(prefix="[metrics] ")
        assert line.startswith("[metrics] ")
        assert "round[" in line and "reports=42" in line
        assert MetricsRegistry().summary_line() == "no samples yet"


# ---------------------------------------------------------------------------
# trace files: Chrome export, validation
# ---------------------------------------------------------------------------


class TestTraceFiles:
    def test_chrome_trace_lanes_and_rebase(self):
        tr = Tracer(source="coord")
        t0 = tr.now()
        tr.complete("round", "collect", t0, 0.001, {"step": 1})
        tr.ingest("xeon0#0", [[5.0, 0.5, "worker", "step", "X", None]],
                  recv_ts=tr.now())
        doc = chrome_trace(tr.events())
        evs = doc["traceEvents"]
        meta = [e for e in evs if e["ph"] == "M"]
        assert {m["args"]["name"] for m in meta} == {"coord", "xeon0#0"}
        body = [e for e in evs if e["ph"] != "M"]
        assert min(e["ts"] for e in body) == 0.0     # rebased to µs from 0
        assert all(e["pid"] == 1 for e in body)

    def test_chrome_sink_roundtrip_and_validate(self, tmp_path):
        path = str(tmp_path / "trace.json")
        tr = Tracer(source="coord", sinks=[ChromeTraceSink(path)])
        with tr.span("round", "r", {"step": 0}):
            tr.instant("control", "retune", {"group": "g"})
        tr.close()
        with open(path) as f:
            assert "traceEvents" in json.load(f)
        events = load_trace(path)
        assert validate_events(events) == []
        names = {(e["src"], e["name"]) for e in events}
        assert ("coord", "retune") in names and ("coord", "r") in names
        # durations back in seconds
        span = next(e for e in events if e["name"] == "r")
        assert 0.0 <= span["dur"] < 1.0

    def test_load_trace_refuses_other_files(self, tmp_path):
        lines = tmp_path / "trace.jsonl"
        lines.write_text('{"ts": 0, "name": "a", "ph": "i"}\n'
                         '{"ts": 1, "name": "b", "ph": "i"}\n')
        other = tmp_path / "other.json"
        other.write_text('{"events": []}')
        for path in (lines, other):
            with pytest.raises(ValueError):
                load_trace(str(path))

    def test_validate_events_catches_malformed(self):
        assert validate_events([]) == ["trace contains no events"]
        bad = [{"ts": -1.0, "ph": "Q", "name": ""},
               {"ts": 1.0, "ph": "X", "name": "s", "dur": float("nan")}]
        problems = validate_events(bad)
        assert len(problems) >= 3
        assert validate_events([TraceEvent(1.0, "c", "n")]) == []


# ---------------------------------------------------------------------------
# satellite: TelemetryBus subscriber isolation
# ---------------------------------------------------------------------------


class TestTelemetryBusIsolation:
    def test_subscriber_exception_never_breaks_publish(self):
        bus = TelemetryBus()
        seen = []

        def broken(rep):
            raise ValueError("observer bug")

        bus.subscribe(broken)
        bus.subscribe(seen.append)           # AFTER the broken one
        rep = StepReport(step=3, group="g", speed=10.0)
        bus.publish(rep)                     # must not raise
        assert seen == [rep]                 # later observers still ran
        assert bus.drain() == {"g": rep}     # the round still has data
        (err,) = bus.errors
        assert err["group"] == "g" and err["step"] == 3
        assert "broken" in err["subscriber"]
        assert "ValueError" in err["error"]

    def test_subscriber_errors_are_bounded_and_traced(self):
        bus = TelemetryBus()
        bus.tracer = Tracer()
        bus.subscribe(lambda rep: (_ for _ in ()).throw(KeyError("x")))
        for step in range(300):
            bus.publish(StepReport(step=step, group="g", speed=1.0))
        assert len(bus.errors) == 256        # bounded, publish kept going
        traced = [e for e in bus.tracer.events()
                  if (e.cat, e.name) == ("error", "subscriber")]
        assert traced and traced[0].args["error"].startswith("KeyError")


# ---------------------------------------------------------------------------
# satellite: StepBuckets depth hook
# ---------------------------------------------------------------------------


class TestStepBucketsDepth:
    def test_depth_hook_fires_on_add_and_pop(self):
        b = StepBuckets()
        depths = []
        b.on_depth = depths.append
        b.add(0, "a", 1)
        b.add(1, "a", 1)
        b.add(0, "b", 1)
        assert depths == [1, 2, 2]
        b.pop(0)
        assert depths[-1] == 1
        b.pop(1)
        assert depths[-1] == 0
        assert b.add(0, "late", 1) is False  # stale: below the floor
        assert depths[-1] == 0               # rejected arrivals don't fire

    def test_depth_gauge_wiring(self):
        mx = MetricsRegistry()
        b = StepBuckets()
        b.on_depth = mx.gauge("coord.bucket_depth").set
        b.add(4, "g", 1)
        assert mx.gauge("coord.bucket_depth").value == 1
        b.pop(4)
        assert mx.gauge("coord.bucket_depth").value == 0


# ---------------------------------------------------------------------------
# wire shapes: obs piggyback is invisible until used
# ---------------------------------------------------------------------------


class TestObsWireShape:
    def test_obs_omitted_at_default_pins_legacy_shape(self):
        _, fields = StepReportMsg(7, "g", 31.13, batch_size=180).to_wire()
        assert "obs" not in fields
        _, fields = ReportBatch.pack(
            [StepReportMsg(1, "g", 8.0, batch_size=8)]).to_wire()
        assert "obs" not in fields
        _, fields = CheckpointAck(12, "g", 12, 140).to_wire()
        assert "obs" not in fields

    def test_batch_report_tuples_keep_pre_obs_arity(self):
        batch = ReportBatch.pack([StepReportMsg(1, "g", 8.0, batch_size=8),
                                  StepReportMsg(2, "g", 8.5, batch_size=8)])
        assert all(len(values) == 8 for values in batch.reports)
        assert [m.step for m in batch.unpack()] == [1, 2]

    @pytest.mark.parametrize("name", sorted(CODECS))
    def test_obs_payload_roundtrips_every_codec(self, name):
        codec = CODECS[name]
        wire_events = [[1.5, 0.25, "worker", "step", "X", {"step": 7}],
                       [1.8, 0.0, "worker", "throttled", "i", None]]
        for msg in (StepReportMsg(7, "g", 31.13, batch_size=180,
                                  obs=wire_events),
                    ReportBatch.pack([StepReportMsg(1, "g", 8.0)]),
                    CheckpointAck(12, "g", 12, 140, obs=wire_events)):
            if isinstance(msg, ReportBatch):
                msg.obs = wire_events
            got = Message.from_wire(
                codec.decode(codec.encode(msg.to_wire())))
            assert got == msg and got.obs == wire_events


# ---------------------------------------------------------------------------
# tentpole acceptance: traced runs keep exact parity, timelines merge
# ---------------------------------------------------------------------------


def _traced_fig6(staleness: int):
    tracer = Tracer(source="coord")
    metrics = MetricsRegistry()
    p = fig6_parity(manager="local", staleness=staleness,
                    tracer=tracer, metrics=metrics)
    return p, tracer, metrics


class TestTracedParity:
    def test_fig6_exact_parity_traced_and_untraced(self):
        """The sacred gate, both ways: tracing must be provably inert —
        the traced run and the untraced run both match the simulator
        trace event-for-event."""
        p_traced, tracer, _ = _traced_fig6(staleness=0)
        p_plain = fig6_parity(manager="local")
        assert p_traced["match"], (p_traced["sim"], p_traced["runtime"])
        assert p_plain["match"]
        assert p_traced["runtime"] == p_plain["runtime"]
        assert tracer.events(), "traced run recorded nothing"

    def test_fig6_exact_parity_traced_under_runahead(self):
        p, tracer, _ = _traced_fig6(staleness=2)
        assert p["match"], (p["sim"], p["runtime"])
        assert p["result"].retune_lags == [3, 3]

    def test_worker_timelines_merge_into_coordinator_lanes(self):
        _, tracer, _ = _traced_fig6(staleness=0)
        srcs = {e.src for e in tracer.events()}
        assert "coord" in srcs
        worker_lanes = {s for s in srcs if "#" in s}
        assert worker_lanes == {"xeon0#0", "xeon1#0", "xeon2#0"}
        steps = [e for e in tracer.events()
                 if e.src in worker_lanes and e.name == "step"]
        assert steps and all(e.ph == "X" for e in steps)
        assert validate_events(tracer.events()) == []

    def test_retune_events_carry_policy_rationale(self):
        _, tracer, _ = _traced_fig6(staleness=0)
        retunes = [e for e in tracer.events()
                   if (e.cat, e.name) == ("control", "retune")]
        assert len(retunes) == 2
        for ev in retunes:
            a = ev.args
            assert a["policy"] == "speed_decline"
            assert a["rule"] == "decline"
            assert a["observed_speed"] < a["required_speed"]
        assert [(a["old_batch"], a["new_batch"])
                for a in (e.args for e in retunes)] == \
            [(180, 140), (140, 100)]

    def test_round_spans_and_retune_effect_lag(self):
        p, tracer, metrics = _traced_fig6(staleness=0)
        phases = {e.name for e in tracer.events() if e.cat == "round"}
        assert {"grant", "collect", "decide", "broadcast",
                "round"} <= phases
        effects = [e.args for e in tracer.events()
                   if e.name == "retune_effect"]
        assert [a["lag_rounds"] for a in effects] == [1, 1]
        lag = metrics.get("coord.retune_effect_lag_rounds")
        assert lag is not None and lag.count == 2

    def test_registry_matches_runtime_result(self):
        p, _, metrics = _traced_fig6(staleness=0)
        assert metrics.counter("coord.reports").value == \
            p["result"].reports_total
        assert metrics.counter("coord.retunes").value == 2
        lat = metrics.get("coord.round_latency_s")
        assert lat is not None and lat.count == p["result"].rounds
        per_worker = [n for n in metrics.names()
                      if n.startswith("coord.grant_report_latency_s.")]
        assert sorted(per_worker) == \
            ["coord.grant_report_latency_s.xeon0",
             "coord.grant_report_latency_s.xeon1",
             "coord.grant_report_latency_s.xeon2"]


# ---------------------------------------------------------------------------
# satellite: traces stay well-formed under real faults (ProcessManager)
# ---------------------------------------------------------------------------


class TestFaultTraceWellFormed:
    def test_sigkill_run_produces_valid_trace(self):
        """SIGKILL mid-run: the dead worker's un-flushed span simply
        never appears (complete-events-only), the trace validates, the
        fault instants and counters are recorded, and the restarted
        worker shows up as a NEW lane (fresh clock epoch)."""
        tracer = Tracer(source="coord")
        metrics = MetricsRegistry()
        d = dropout_parity(manager="process", fault_mode="kill",
                           tracer=tracer, metrics=metrics)
        assert d["match"], (d["sim"], d["runtime"])
        assert validate_events(tracer.events()) == []
        faults = [e.name for e in tracer.events() if e.cat == "fault"]
        assert "kill" in faults and "restart" in faults
        assert metrics.counter("coord.faults.kill").value == 1
        assert metrics.counter("coord.faults.restart").value == 1
        srcs = {e.src for e in tracer.events()}
        assert "xeon1#1" in srcs             # the second life's lane
        retunes = [e.args for e in tracer.events()
                   if (e.cat, e.name) == ("control", "retune")]
        assert [a["rule"] for a in retunes] == ["bus_silence", "rejoin"]
        assert retunes[0]["policy"] == "liveness"

    def test_sigstop_run_produces_valid_trace(self):
        """SIGSTOP: channel open, zero reports — the wedged window
        leaves a gap, not a malformed trace."""
        tracer = Tracer(source="coord")
        d = dropout_parity(manager="process", fault_mode="suspend",
                           round_timeout=0.2, tracer=tracer)
        assert d["match"], (d["sim"], d["runtime"])
        assert validate_events(tracer.events()) == []
        faults = [e.name for e in tracer.events() if e.cat == "fault"]
        assert "suspend" in faults and "resume" in faults

    def test_kill_trace_exports_to_chrome_json(self, tmp_path):
        """End to end: a fault run's merged timeline loads back from
        the Chrome file and still validates (the CI artifact path)."""
        path = str(tmp_path / "fault_trace.json")
        tracer = Tracer(source="coord", sinks=[ChromeTraceSink(path)])
        d = dropout_parity(manager="local", fault_mode="silence",
                           tracer=tracer)
        assert d["match"]
        tracer.close()
        events = load_trace(path)
        assert validate_events(events) == []
        assert {e["src"] for e in events} >= {"coord"}
