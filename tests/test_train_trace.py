"""The trainer's own spans, counters and named scopes.

``HeteroTrainer.run`` opens one ``train`` span per phase of every step
(``STEP_SPANS``), each also a profiler annotation when the tracer has
``annotate`` set; its ``train.*`` counters follow the plan; the jitted
step is ``jit_hypertune_train_step`` and its device ops carry the
``attention``/``mlp``/``loss_head``/``optimizer`` name scopes, which
change op metadata and nothing else.
"""
from __future__ import annotations

import contextlib
import gc
import os
import pathlib
import re
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_arch, reduced_config
from repro.core import hetero_dp
from repro.core.allocator import solve
from repro.core.speed_model import SpeedModel
from repro.data.pipeline import HeteroPipeline
from repro.launch.train import (STEP_SPANS, HeteroTrainer, TrainerConfig,
                                interference_report_fn)
from repro.obs import MetricsRegistry, Tracer, load_trace
from repro.optim.optimizer import OptConfig

SCOPES = ("attention", "mlp", "loss_head", "optimizer")
SRC = pathlib.Path(__file__).resolve().parents[1] / "src"


def tiny_trainer(**kw) -> HeteroTrainer:
    sm = SpeedModel(np.array([1.0, 2, 4, 8]), np.array([10.0, 18, 28, 30]))
    plan = solve({"g0": (1, sm), "g1": (2, sm)}, dataset_size=4096)
    cfg = TrainerConfig(seq_len=16, dataset_size=4096, log_every=0,
                        opt=OptConfig(lr=5e-3, warmup_steps=0,
                                      schedule="const"), **kw)
    return HeteroTrainer(reduced_config(get_arch("deepseek-7b")), plan, cfg)


class RecordingAnnotation:
    """Stands in for ``jax.profiler.TraceAnnotation``: logs each enter
    and exit with the time it happened."""

    def __init__(self, log: list, name: str) -> None:
        self.log, self.name = log, name

    def __enter__(self):
        self.log.append(("enter", self.name, time.perf_counter()))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.name, time.perf_counter()))
        return False


def _spans(tracer, name=None):
    return [e for e in tracer.events() if e.ph == "X" and e.cat == "train"
            and e.name != "gc" and (name is None or e.name == name)]


def test_each_step_opens_its_spans_in_order_inside_annotations():
    log: list = []
    t = tiny_trainer()
    t.tracer = Tracer(source="train",
                      annotate=lambda name: RecordingAnnotation(log, name))
    t.run(3)
    names = [e.name for e in _spans(t.tracer)]
    assert names == list(STEP_SPANS) * 3
    log = [x for x in log if x[1] != "train.gc"]
    assert [(kind, n) for kind, n, _ in log] == \
        [(kind, f"train.{n}") for n in names for kind in ("enter", "exit")]
    for ev, (_, _, t_in), (_, _, t_out) in zip(
            _spans(t.tracer), log[0::2], log[1::2]):
        assert t_in <= ev.ts and ev.ts + ev.dur <= t_out


def test_losses_and_retunes_identical_with_tracer_on_and_off():
    fn = interference_report_fn({"g1": [(3, 10 ** 9, 0.45)]})
    dts = []

    def recording(step, plan, dt):
        dts.append(dt)
        return fn(step, plan, dt)

    off, on = tiny_trainer(), tiny_trainer()
    on.tracer = Tracer(source="train")
    on.metrics = MetricsRegistry()
    off_recs = off.run(10, report_fn=fn)
    on_recs = on.run(10, report_fn=recording)
    assert [r.loss for r in on_recs] == [r.loss for r in off_recs]
    assert [r.retune for r in on_recs] == [r.retune for r in off_recs]
    assert any(r.retune for r in on_recs)
    # dt is the dispatch and the loss sync, as it was untraced
    for dt, d, s in zip(dts, _spans(on.tracer, "dispatch"),
                        _spans(on.tracer, "sync")):
        assert dt >= d.dur + s.dur


def test_counters_follow_the_plan_and_a_retune_is_a_span():
    t = tiny_trainer()
    t.tracer = Tracer(source="train")
    t.metrics = mx = MetricsRegistry()
    recs = t.run(10, report_fn=interference_report_fn(
        {"g1": [(3, 10 ** 9, 0.45)]}))
    retunes = [r for r in recs if r.retune]
    assert retunes
    snap = mx.snapshot()
    assert snap["train.rows.live"]["value"] == sum(r.global_batch
                                                   for r in recs)
    assert snap["train.rows.capacity"]["value"] == \
        10 * t.control_plane.plan.global_capacity
    assert snap["train.retunes"]["value"] == len(retunes)
    assert len(_spans(t.tracer, "retune")) == len(retunes)
    # the retune span follows the control round of the step it fired in
    names = [e.name for e in _spans(t.tracer)]
    assert all(names[i - 1] == "control" for i, n in enumerate(names)
               if n == "retune")


def test_a_new_shape_is_a_compile_instant_and_counter():
    t = tiny_trainer()
    t.tracer = Tracer(source="train")
    t.metrics = mx = MetricsRegistry()

    def compiles():
        return mx.snapshot().get("train.compiles", {}).get("value", 0)

    t.run(1)
    first = compiles()
    assert first >= 1
    t.run(2)
    assert compiles() == first                  # same shapes: no compile
    plan = t.control_plane.plan
    t.pipeline = HeteroPipeline(plan, 8, t.arch_cfg.vocab_size, seed=1)
    t.run(1)
    assert compiles() > first
    instants = [e for e in t.tracer.events()
                if e.ph == "i" and e.name == "compile"]
    assert len(instants) == compiles()
    assert all(e.args["seconds"] > 0 for e in instants)


def test_attention_paths_are_counted_while_the_step_is_built():
    t = tiny_trainer()
    t.metrics = mx = MetricsRegistry()

    def paths():
        snap = mx.snapshot()
        return {k: snap.get(f"kernels.attention.{k}", {}).get("value", 0)
                for k in ("fused", "blocked")}

    t.run(1)
    built = paths()
    # the CPU backend keeps every call on the blocked path
    assert built["blocked"] >= 1 and built["fused"] == 0
    t.run(2)
    assert paths() == built                 # no new trace, no new count


def test_untraced_run_records_nothing():
    t = tiny_trainer()
    assert not t.tracer and t.metrics is None
    t.run(2)
    assert t.tracer.events() == []


def test_garbage_collection_inside_run_is_a_gc_span():
    t = tiny_trainer()
    t.tracer = Tracer(source="train")
    report = HeteroTrainer._healthy_reports

    def collecting(step, plan, dt):
        gc.collect()
        return report(plan)

    t.run(2, report_fn=collecting)
    gcs = [e for e in t.tracer.events() if e.name == "gc"]
    assert len(gcs) >= 2
    assert all(e.ph == "X" and "collected" in e.args for e in gcs)


# -- the compiled step -------------------------------------------------------


def _step_hlo(t: HeteroTrainer) -> str:
    rows = t.control_plane.plan.global_capacity
    batch = {"tokens": jnp.zeros((rows, t.cfg.seq_len), jnp.int32),
             "targets": jnp.zeros((rows, t.cfg.seq_len), jnp.int32),
             "sample_mask": jnp.ones((rows,), jnp.float32)}
    step = jax.jit(hetero_dp.make_train_step(t.model, t.opt))
    return step.lower(t.params, t.opt_state, batch).compile().as_text()


def _without_metadata(hlo: str) -> str:
    """The HLO's computations alone: no module name, no op metadata, no
    source-location tables."""
    hlo = re.sub(r", metadata=\{[^}]*\}", "", hlo)
    hlo = re.sub(r"^HloModule \S+", "HloModule", hlo, flags=re.M)
    lines = hlo.split("\n")
    if "FileNames" in lines:
        i = lines.index("FileNames")
        k = lines.index("StackFrames", i)
        j = lines.index("", k)
        lines = lines[:i] + lines[j:]
    return "\n".join(lines)


@pytest.fixture(scope="module")
def scoped_hlo():
    return _step_hlo(tiny_trainer())


def _scopes(hlo: str) -> list:
    """The scopes each op's ``op_name`` holds as a component of its path,
    ``transpose(jvp(attention))`` counting as ``attention``."""
    out = []
    for op_name in re.findall(r'op_name="([^"]*)"', hlo):
        parts = {re.sub(r"^(?:[\w.]+\()+|\)+$", "", c)
                 for c in op_name.split("/")}
        out.append((op_name, parts & set(SCOPES)))
    return out


def test_step_module_is_named_and_has_ops_under_each_scope(scoped_hlo):
    assert scoped_hlo.startswith("HloModule jit_hypertune_train_step")
    found = _scopes(scoped_hlo)
    for scope in SCOPES:
        assert any(scope in s for _, s in found), f"no op under {scope}"
    # the backward pass keeps the forward op's scope
    assert any("transpose(" in n and "attention" in s for n, s in found)
    assert any("transpose(" in n and "mlp" in s for n, s in found)


def test_scopes_change_metadata_only(scoped_hlo, monkeypatch):
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = _step_hlo(tiny_trainer())
    assert not any(s for _, s in _scopes(plain))
    assert _without_metadata(plain) == _without_metadata(scoped_hlo)


# -- the inproc CLI ----------------------------------------------------------


def test_inproc_cli_writes_a_trace_that_summarizes(tmp_path):
    path = tmp_path / "run.json"
    cmd = [sys.executable, "-m", "repro.launch.train",
               "--steps", "3", "--seq-len", "8", "--groups", "host:1",
               "--runtime", "inproc", "--trace", str(path)]
    env = {**os.environ, "PYTHONPATH": str(SRC)}
    done = subprocess.run(cmd, capture_output=True, text=True,
                          timeout=600, env=env)
    assert done.returncode == 0, done.stderr[-2000:]
    events = load_trace(str(path))
    spans = [e["name"] for e in events
             if e["ph"] == "X" and e["cat"] == "train" and e["name"] != "gc"]
    assert spans[:len(STEP_SPANS)] == list(STEP_SPANS)
    assert spans.count("dispatch") == 3
    out = subprocess.run([sys.executable, "-m", "repro.launch.obs",
                          "summarize", str(path)],
                         capture_output=True, text=True, timeout=120,
                         env=env)
    assert out.returncode == 0, out.stderr
    assert "train/dispatch [train]" in out.stdout
