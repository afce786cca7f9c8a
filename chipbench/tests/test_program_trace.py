"""The split of a traced window by the program's spans and name scopes
(``program_trace.py``): op-to-region attribution from HLO text, idle
gaps split over the spans that overlap them, and the reduction of the
recorded v5e trace, which holds no program spans."""
import pathlib

import pytest

from chipbench import program_trace as pt
from chipbench import trace_reduce as tr

SMALL = pathlib.Path(__file__).parent / "data" / "small.xplane.pb"

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


def test_every_instruction_is_named_loop_bodies_included():
    names = pt.hlo_op_names(HLO)
    assert "copy.5" not in names                  # no metadata
    assert names["bitcast_dynamic-update-slice_fusion.21"].endswith(
        "transpose(jvp(attention))/dot_general")
    assert {pt.region_of(names[op]) for op in names} >= set(pt.SCOPES)


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
    assert pt.region_of(pt.hlo_op_names(HLO).get(op, "")) == region


def test_region_seconds_sum_every_op():
    secs = {"fusion.12": 3.0, "convolution.4": 2.0, "fusion.259": 1.0,
            "fusion.30": 0.5, "while.89": 0.25, "unknown.1": 0.125}
    out = pt.region_seconds(secs, pt.hlo_op_names(HLO))
    assert out == {"attention": 2.0, "mlp": 3.0, "loss_head": 0.5,
                   "optimizer": 1.0, "other": 0.375}


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
    assert pt.split_gap(TIMELINE, *gap) == split


def test_idle_by_span_adds_the_gaps_up():
    gaps = [(15, 35), (95, 105)]
    out = pt.idle_by_span(gaps, TIMELINE)
    assert out == {"train.input": 5, "train.h2d": 5, "train.dispatch": 5,
                   "train.sync": 5, "step": 5, "between": 5}
    # by midpoints the first gap would all be train.dispatch's
    assert TIMELINE.at(25) == "train.dispatch"


def test_recorded_trace_without_program_spans():
    """The recorded trace (three annotated steps of four small programs)
    has no ``train.*`` spans and no HLO to name its ops: the window and
    busy time are the benchmark's own, all idle time is ``step`` or
    ``between``, and every op is ``other``."""
    path = tr.find_xplane(str(SMALL.parent))
    bench = tr.reduce(path, "chipbench.")
    out = pt.reduce_program(path, "", "chipbench.")
    assert out["window_s"] == bench["window_s"]
    assert out["busy_s"] == pytest.approx(bench["busy_s"], abs=1e-12)
    assert out["n_steps"] == bench["n_steps"] == 3
    assert set(out["idle_by_span"]) <= {"step", "between"}
    assert sum(out["idle_by_span"].values()) == pytest.approx(
        out["idle_s"], rel=1e-9)
    assert out["region_ms"]["other"] == pytest.approx(
        1e3 * out["busy_s"] / 3, rel=1e-9)
    assert out["ops_named"] == 0
    assert [g["s"] for g in out["idle_gaps"][:2]] == pytest.approx(
        [g[1] for g in bench["idle_gaps"][:2]], abs=1e-12)
