"""The trace reduction: interval arithmetic by hand; ops named from HLO
text and selected by name scope; idle gaps split over the spans that
overlap them; the readers on a synthetic reduction; and a small trace
recorded on a TPU v5 lite (``data/small.xplane.pb``): three annotated
steps, each an ``input`` span of a 15 ms host sleep, four jitted
matmul programs, and a ``control`` span of an 8 ms sleep. It holds no
program spans and no HLO names its ops."""
import pathlib

import pytest

from chipbench import cells, harness
from chipbench import trace_reduce as tr

DATA = pathlib.Path(__file__).parent / "data"
SMALL = DATA / "small.xplane.pb"
# two instructions of a yi9b-2l step compiled for a described v5e, the
# kernel's serialized body cut short: a Pallas custom call's text spans
# three lines
SPLASH = (DATA / "splash_custom_call.hlo").read_text()
SCOPES = ("attention", "mlp", "loss_head", "optimizer")


def test_union_merges_overlaps_and_touching():
    assert tr.union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 9)]) == \
        [(0, 4), (5, 9)]


def test_clip_and_gaps():
    busy = tr.union(tr.clip([(0, 2), (3, 5), (8, 12)], 1, 10))
    assert busy == [(1, 2), (3, 5), (8, 10)]
    assert tr.gaps(busy, 1, 10) == [(2, 3), (5, 8)]
    assert tr.gaps([], 1, 10) == [(1, 10)]


def test_timeline_innermost_span():
    t = tr.Timeline({"step": [(0, 10), (12, 20)],
                     "input": [(1, 3), (13, 14)], "control": [(8, 9)]})
    assert [t.at(x) for x in (-1, 0.5, 2, 5, 8.5, 9.5, 11, 13.5, 15, 21)] == \
        ["between", "step", "input", "step", "control", "step", "between",
         "input", "step", "between"]


def test_self_times_take_nested_ops_out_of_their_parent():
    events = [("loop", 0, 10), ("body", 2, 5), ("body", 6, 8), ("after", 10, 12)]
    assert tr.self_times(events, 0, 11) == {"loop": 5, "body": 5, "after": 1}


# Counted by hand from the recorded trace's raw events: the three host
# step spans run from 46,582,206 ns to 122,158,611 ns; the device's XLA
# Ops line holds 48 operations (4 programs a step, each a copy-start, a
# copy-done and two fusions), none overlapping, all inside the window,
# 2,296,015 ns in all. The longest idle stretches are the host sleeps:
# 24,179,560 and 23,947,292 ns whose middles fall in the input spans of
# steps 3 and 2, 14,427,959 ns before the first operation (input of step
# 1), and 10,188,843 ns after the last (control of step 3). The device
# clock runs about 1.2 ms ahead of the host's in this trace, so the
# programs appear inside the input spans that dispatched them.
def test_recorded_trace_counted_by_hand():
    out = tr.reduce(tr.find_xplane(str(SMALL.parent)), "chipbench.")
    assert out["n_steps"] == 3
    assert out["devices"] == 1
    assert out["window_s"] == pytest.approx(75_576_405e-9, abs=1e-9)
    assert out["busy_s"] == pytest.approx(2_296_015e-9, abs=1e-9)
    assert [g[0] for g in out["idle_gaps"][:4]] == \
        ["input", "input", "input", "control"]
    assert [g[1] for g in out["idle_gaps"][:4]] == pytest.approx(
        [24_179_560e-9, 23_947_292e-9, 14_427_959e-9, 10_188_843e-9], abs=1e-9)
    assert sum(out["idle_by_label"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-9)
    names = [n for n, _ in out["device_ops"]]
    assert names == ["fusion", "convolution_tanh_fusion", "copy-done",
                     "copy-start"]
    assert out["device_ops"][0][1] == pytest.approx(1_090_473e-9, abs=1e-9)


HLO = """HloModule jit_hypertune_train_step, is_scheduled=true

%fused_computation.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %mul.3 = f32[8]{0} multiply(%p, %p), metadata={op_type="mul" op_name="jit(hypertune_train_step)/optimizer/mul"}
}

%body.7 (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.12 = bf16[2,8]{1,0} fusion(%a), kind=kOutput, calls=%fused_computation.2, metadata={op_type="dot_general" op_name="jit(hypertune_train_step)/transpose(jvp())/while/body/closed_call/checkpoint/mlp/dot_general" source_file="layers.py" source_line=280}
  %convolution.4 = f32[2,8]{1,0} convolution(%b, %c), metadata={op_type="dot_general" op_name="jit(hypertune_train_step)/jvp()/while/body/closed_call/attention/closed_call/while/body/dot_general"}
  %bitcast_dynamic-update-slice_fusion.21 = f32[2,8]{1,0} fusion(%d), kind=kLoop, metadata={op_name="jit(f)/transpose(jvp(attention))/dot_general"}
  ROOT %tuple.9 = (s32[], f32[8]) tuple(%i, %x)
}

ENTRY %main.90 (params__embed: f32[8]) -> f32[8] {
  %params__mlp.1 = f32[8]{0} parameter(0), metadata={op_name="params[\'layers\'][\'mlp\'][\'w_up\']"}
  %while.89 = (s32[], f32[8]) while(%t), condition=%cond, body=%body.7, metadata={op_type="while" op_name="jit(hypertune_train_step)/transpose(jvp())/while"}
  %fusion.259 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation.1, metadata={op_type="add" op_name="jit(hypertune_train_step)/optimizer/add"}
  %fusion.30 = f32[4,8]{1,0} fusion(%h), kind=kLoop, metadata={op_name="jit(hypertune_train_step)/loss_head/reduce_max"}
  %copy.5 = f32[8]{0} copy(%x)
  ROOT %tuple.1 = (f32[8]) tuple(%fusion.259)
}
"""


def _scope(op_name):
    return next((s for s in SCOPES if s in tr.scopes_of(op_name)), "other")


def test_every_instruction_is_named_loop_bodies_included():
    names = tr.hlo_op_names(HLO)
    assert "copy.5" not in names                  # no metadata
    assert names["bitcast_dynamic-update-slice_fusion.21"].endswith(
        "transpose(jvp(attention))/dot_general")
    assert {_scope(names[op]) for op in names} >= set(SCOPES)


def test_custom_call_spanning_lines_is_named():
    """The splash kernel's instruction gives its ``op_name`` on its third
    line: the lines are joined before the name is read, and the kernel
    is in the ``attention`` scope, under its own kernel name."""
    names = tr.hlo_op_names(SPLASH)
    assert set(names) == {"splash_mha_fwd_residuals.18",
                          "vmap_jit__splash_attention__.50", "fusion.412"}
    scopes = tr.scopes_of(names["splash_mha_fwd_residuals.18"])
    assert {"attention", "splash_mha_fwd_residuals", "pallas_call"} <= scopes
    assert names["fusion.412"].endswith("/attention/transpose")


@pytest.mark.parametrize("op,region", [
    ("fusion.12", "mlp"),                         # backward, in a loop body
    ("convolution.4", "attention"),               # nested loop body
    ("bitcast_dynamic-update-slice_fusion.21", "attention"),  # wrapped
    ("fusion.259", "optimizer"),
    ("mul.3", "optimizer"),                       # inside a fusion
    ("fusion.30", "loss_head"),
    ("while.89", "other"),                        # the loop itself
    ("params__mlp.1", "other"),                   # a parameter's path
    ("copy.5", "other"),                          # no op_name
])
def test_region_of_each_op(op, region):
    assert _scope(tr.hlo_op_names(HLO).get(op, "")) == region


def _summary(op_s, hlo=HLO, spans=None, n_steps=2):
    names = tr.hlo_op_names(hlo)
    return {"busy_s": sum(op_s.values()), "window_s": 10.0,
            "n_steps": n_steps, "op_s": op_s,
            "op_names": {n: names[n] for n in op_s if n in names},
            "span_s": spans or {}}


def test_region_seconds_sum_every_op():
    secs = {"fusion.12": 3.0, "convolution.4": 2.0, "fusion.259": 1.0,
            "fusion.30": 0.5, "while.89": 0.25, "unknown.1": 0.125}
    t = _summary(secs)
    out = {s: tr.scope_ms(t, s) for s in SCOPES}
    assert out == {"attention": 1000.0, "mlp": 1500.0, "loss_head": 250.0,
                   "optimizer": 500.0}
    # what no scope holds is the rest of the busy time
    assert 1e3 * t["busy_s"] / 2 - sum(out.values()) == 187.5


def _ctx(trace, memory=0):
    return harness.Context([], 1.0, 1.0, 8, {}, None, {}, 1, memory, trace)


READERS = {"attn_device_ms": 1000.0, "mlp_device_ms": 1500.0,
           "loss_head_device_ms": 250.0, "optimizer_device_ms": 500.0,
           "h2d_ms": 0.5, "dispatch_ms": 1.25}


def test_readers_select_scopes_and_spans():
    t = _summary({"fusion.12": 3.0, "convolution.4": 2.0, "fusion.259": 1.0,
                  "fusion.30": 0.5},
                 spans={"train.h2d": 0.001, "train.dispatch": 0.0025,
                        "train.sync": 9.0})
    got = {k: cells.reader(k)(_ctx(t)) for k in READERS}
    assert got == READERS
    assert cells.reader("hbm_peak_gb")(_ctx(t, 12_257_000_000)) == 12.257


@pytest.mark.parametrize("trace", [
    None,                                                 # an untraced run
    _summary({}, spans={}),                               # nothing traced
    _summary({"fusion.12": 1.0}, hlo=""),                 # no HLO
    _summary({"while.89": 1.0, "copy.5": 1.0}),           # no op in a scope
], ids=["untraced", "empty", "no_hlo", "no_scoped_op"])
def test_readers_return_none_without_their_input(trace):
    for name in READERS:
        assert cells.reader(name)(_ctx(trace)) is None, name
    assert cells.reader("hbm_peak_gb")(_ctx(trace, 0)) is None


TIMELINE = tr.Timeline({
    "step": [(0, 100)],
    "train.input": [(10, 20)], "train.h2d": [(20, 25)],
    "train.dispatch": [(25, 30)], "train.sync": [(30, 90)],
})


@pytest.mark.parametrize("gap,split", [
    ((15, 35), {"train.input": 5, "train.h2d": 5, "train.dispatch": 5,
                "train.sync": 5}),
    ((95, 105), {"step": 5, "between": 5}),
    ((-5, 12), {"between": 5, "step": 10, "train.input": 2}),
    ((21, 24), {"train.h2d": 3}),
])
def test_a_gap_is_split_over_the_spans_it_overlaps(gap, split):
    assert tr.split_gap(TIMELINE, *gap) == split


def test_idle_by_span_adds_the_gaps_up():
    gaps = [(15, 35), (95, 105)]
    out = tr.idle_by_span(gaps, TIMELINE)
    assert out == {"train.input": 5, "train.h2d": 5, "train.dispatch": 5,
                   "train.sync": 5, "step": 5, "between": 5}
    # by midpoints the first gap would all be train.dispatch's
    assert TIMELINE.at(25) == "train.dispatch"


def test_recorded_trace_without_program_spans():
    """No ``train.*`` spans and no HLO to name the ops: all idle time is
    ``step`` or ``between``, every op's self time adds up to the busy
    time, and no reader of a scope or a span finds anything."""
    out = tr.reduce(tr.find_xplane(str(SMALL.parent)), "chipbench.", "")
    assert set(out["idle_by_span"]) <= {"step", "between"}
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["window_s"] - out["busy_s"], rel=1e-9)
    assert sum(out["op_s"].values()) == pytest.approx(out["busy_s"], rel=1e-9)
    assert out["op_names"] == {} and out["span_s"] == {}
    for name in READERS:
        assert cells.reader(name)(_ctx(out)) is None, name
