"""A ``--trace 1`` run, with the harness's look for a chip skipped: the
program's tracer and a metrics registry are attached for the window
only, the reduction gets the compiled step's HLO, and the readers of the
program's spans find them. The CPU has no TPU plane, so every reader of
device time finds nothing."""
import time

import jax

from chipbench import harness, trace_reduce
from chipbench.tests.tiny import tiny_cell

SEED = 2**31 + 123
DEVICE_READERS = ("step_device_ms", "step_roofline", "device_idle_pct",
                  "attn_device_ms", "mlp_device_ms", "loss_head_device_ms",
                  "optimizer_device_ms", "hbm_peak_gb")


def test_traced_run_hands_readers_the_program_instrumentation(monkeypatch):
    seen = {}
    reduce, step_hlo = trace_reduce.reduce, harness.step_hlo

    def keep(name, fn):
        def wrapped(*a, **k):
            seen[name] = fn(*a, **k)
            return seen[name]
        return wrapped

    monkeypatch.setattr(trace_reduce, "reduce", keep("summary", reduce))
    monkeypatch.setattr(harness, "step_hlo", keep("hlo", step_hlo))
    name = "ds7b-2l.s256.straggler"
    cell = tiny_cell(name)
    res = harness.run(name, SEED, 0.5, True, time.perf_counter(),
                      devices=jax.devices(), cell=cell)
    assert res["correct"], res["checks"]
    got = set(res["metrics"])
    assert {"input_ms", "control_ms", "live_row_pct", "h2d_ms",
            "dispatch_ms"} <= got
    assert not got & set(DEVICE_READERS)

    s = seen["summary"]
    assert {"train.input", "train.h2d", "train.dispatch", "train.sync",
            "train.report", "train.control"} <= set(s["span_s"])
    assert res["metrics"]["h2d_ms"]["value"] == \
        1e3 * s["span_s"]["train.h2d"] / s["n_steps"]
    # the window's rows: 6 of 12 live after the retune in set-up
    assert s["counters"]["train.rows.live"] == 6 * res["attempted"]
    assert s["counters"]["train.rows.capacity"] == 12 * res["attempted"]
    names = trace_reduce.hlo_op_names(seen["hlo"])
    scoped = set().union(*map(trace_reduce.scopes_of, names.values()))
    assert {"attention", "mlp", "loss_head", "optimizer"} <= scoped
