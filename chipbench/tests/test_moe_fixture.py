"""A configuration whose program is not a dense decoder passes the
architecture check and reaches every end-to-end reader through its own
work module, on the CPU, with nothing under ``chipbench/`` told of it
(``tests/moe.py``)."""
import math
import time

import pytest

from chipbench import cells, flops, harness
from chipbench.tests import moe

SEED = 2**31 + 41
V5E = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_load_cell_refuses_a_file_without_work(tmp_path):
    import json

    config = moe.config()
    del config["work"]
    (tmp_path / "c.json").write_text(json.dumps(config))
    bench = {"configs": [{"name": "c", "file": str(tmp_path / "c.json")}],
             "workloads": [{"name": "w", "config": "c",
                            "traffic": "s256.steady", "chips": 1}],
             "end_to_end": [], "per_layer": []}
    with pytest.raises(ValueError, match="no work module"):
        cells.load_cell("w", bench)


def test_moe_configuration_reaches_every_end_to_end_reader():
    cell = moe.cell()
    t0 = time.perf_counter()
    p = harness.set_up(cell, SEED)
    setup_s = time.perf_counter() - t0
    assert p.trainer.arch_cfg.moe.num_experts == 4
    assert all(math.isfinite(x) for x in p.readings["losses"])
    steps, window_s = harness.window(p, 0.3)
    assert p.trainer.step_fn._cache_size() == 1
    work = cells.module(cell.config["work"])
    assert work is moe
    ctx = harness.Context(steps, window_s, setup_s, moe.SEQ_LEN,
                          cell.config["model"], work, V5E, 1)
    got = harness.read_metrics(cell.end_to_end, ctx)
    assert set(got) == {m["name"] for m in cell.end_to_end}
    tokens = sum(s.live_rows for s in steps) * moe.SEQ_LEN
    per_token = moe.train_flops_per_token(cell.config["model"], moe.SEQ_LEN)
    assert got["mfu"]["value"] == 100.0 * tokens * per_token / (
        window_s * V5E["bf16_flops_per_s"])
    # the experts' count, not the dense decoder's
    assert per_token != flops.train_flops_per_token(cell.config["model"],
                                                    moe.SEQ_LEN)
    assert got["tokens_per_s"]["value"] == tokens / window_s
    assert got["setup_s"]["value"] == setup_s
