"""Stannis runtime through REAL worker processes (spawn context).

The fault path here is the genuine article: SIGKILL produces channel
EOF, SIGSTOP produces an open-but-silent channel — in both cases the
coordinator's bus simply receives nothing and the existing liveness
path masks the group out, exactly like the simulator's Dropout model.

Acceptance (ISSUE 2): process-runtime Fig. 6 == sim Fig. 6 retune
sequence; ProcessManager kill/restart == sim Dropout failure/recover
pair; workers run real jitted train steps and never recompile across a
retune.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core.allocator import solve
from repro.core.control import ControlPlane, SpeedDeclinePolicy
from repro.core.speed_model import SpeedModel
from repro.runtime import (MANAGERS, EventLoop, FaultAction, ProcessManager,
                           WorkerFailed, specs_from_plan)
from repro.runtime.parity import dropout_parity, fig6_parity


class TestProcessTraceParity:
    def test_fig6_exact_sequence_through_processes(self):
        p = fig6_parity(manager="process")
        assert p["match"], (p["sim"], p["runtime"])
        assert [(ob, nb) for (_, _, ob, nb, _) in p["runtime"]] == \
            [(180, 140), (140, 100)]

    def test_sigkill_restart_matches_sim_dropout(self):
        """Process kill -> liveness mask-out -> restart -> knee rejoin,
        event-for-event identical to the equivalent ClusterSim Dropout
        run (satellite: runtime fault path end-to-end)."""
        d = dropout_parity(manager="process", fault_mode="kill")
        assert d["match"], (d["sim"], d["runtime"])
        assert d["runtime"] == [(7, "xeon1", 180, 0, "failure"),
                                (20, "xeon1", 0, 180, "recover")]

    def test_sigstop_resume_matches_sim_dropout(self):
        """A wedged (SIGSTOPped) node: channel open, zero reports. Only
        silence-derived liveness can catch this failure mode."""
        d = dropout_parity(manager="process", fault_mode="suspend",
                           round_timeout=0.2)
        assert d["match"], (d["sim"], d["runtime"])


class TestProcessBoundedStaleness:
    def test_fig6_parity_under_runahead_through_processes(self):
        """Bounded-staleness pacing over REAL processes: the decision
        steps and batches match ClusterSim(staleness=2) exactly, and
        the retune reaches the run-ahead workers in k+1 rounds."""
        p = fig6_parity(manager="process", staleness=2)
        assert p["match"], (p["sim"], p["runtime"])
        assert [(ob, nb) for (_, _, ob, nb, _) in p["runtime"]] == \
            [(180, 140), (140, 100)]
        assert p["result"].retune_lags == [3, 3]

    def test_sigkill_under_runahead_still_masked(self):
        """SIGKILL at round 5 with k=2: the dead process may have
        pre-delivered up to 2 run-ahead reports, so bus-silence
        liveness fires within [7, 9] — deferred by at most k rounds,
        never suppressed — and the restart rejoins at the knee."""
        d = dropout_parity(manager="process", fault_mode="kill",
                           staleness=2)
        events = d["runtime"]
        assert [(g, r) for (_, g, _, _, r) in events] == \
            [("xeon1", "failure"), ("xeon1", "recover")]
        fail, recover = events
        assert 7 <= fail[0] <= 9, events
        assert fail[2:4] == (180, 0)
        assert recover == (20, "xeon1", 0, 180, "recover")


@pytest.mark.slow
class TestProcessRealTraining:
    def test_jitted_workers_report_and_never_recompile(self):
        """Two process workers run hetero_dp.make_train_step for real;
        a mid-run kill/restart cycle flows through; CheckpointAck proves
        the retunes never triggered a recompile."""
        sm = SpeedModel(np.array([1.0, 2, 4, 8]),
                        np.array([10.0, 18, 28, 30]))
        plan = solve({"a": (1, sm), "b": (1, sm)}, 4096)
        cp = ControlPlane(plan, [SpeedDeclinePolicy()], liveness_timeout=3)
        specs = specs_from_plan(plan, train={"arch": "deepseek-7b",
                                             "seq_len": 32, "reduced": True})
        manager = ProcessManager()
        loop = EventLoop(cp, manager, round_timeout=120.0)
        try:
            manager.start(specs)
            res = loop.run(12, faults=[FaultAction(3, "kill", "b"),
                                       FaultAction(8, "restart", "b")],
                           checkpoint_every=11)
        finally:
            loop.shutdown()
        assert [e.reason for e in res.events] == ["failure", "recover"]
        assert res.events[0].new_batch == 0
        assert res.events[1].new_batch == 8      # knee restore
        # real execution: measured wall time and loss flow back
        live = [s for s in res.round_stats if s.n_reports]
        assert live, "no reports collected"
        acks = {a.group: a for a in res.checkpoint_acks}
        assert acks and all(a.n_compiles == 1 for a in acks.values())
        # worker "a" trained every round; "b" lost its first life's steps
        assert acks["a"].worker_step >= 11


class TestWorkerInitFailure:
    @pytest.mark.parametrize("runtime", ["local", "process"])
    def test_executor_failure_fails_the_run(self, runtime):
        """A worker whose TrainExecutor cannot be built says why in its
        Goodbye and the run fails — it is not read as a dropout that the
        liveness rule masks out while the run "completes"."""
        sm = SpeedModel(np.array([1.0, 2, 4]), np.array([10.0, 18, 28]))
        plan = solve({"a": (1, sm)}, 4096)
        cp = ControlPlane(plan, [SpeedDeclinePolicy()], liveness_timeout=3)
        specs = specs_from_plan(plan, train={"arch": "no-such-arch",
                                             "seq_len": 8})
        manager = MANAGERS[runtime]()
        loop = EventLoop(cp, manager, round_timeout=60.0)
        try:
            manager.start(specs)
            with pytest.raises(WorkerFailed, match="no-such-arch"):
                loop.run(4)
        finally:
            loop.shutdown()
        assert not manager.workers["a"].alive
        if runtime == "process":
            assert manager._procs["a"].exitcode not in (0, None)
