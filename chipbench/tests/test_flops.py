"""The FLOP and byte counts against the program's parameter count and a
count by hand, the readers that take them through a configuration's work
module, and the peaks table."""
import json

import pytest

from chipbench import cells, flops, harness

BENCH = json.loads(cells.BENCHMARK.read_text())
HAND = {"family": "dense", "num_layers": 1, "d_model": 8, "num_heads": 2,
        "num_kv_heads": 1, "head_dim": 4, "d_ff": 16, "vocab_size": 10,
        "activation": "swiglu", "tie_embeddings": False}


def test_hand_count_tiny():
    # q, o: 8*2*4 = 64 each; k, v: 8*1*4 = 32 each; SwiGLU 3*8*16 = 384
    assert flops.layer_matmul_params(HAND) == 64 + 64 + 32 + 32 + 384
    # plus the LM head 8*10
    assert flops.matmul_params(HAND) == 576 + 80
    # plus the embedding 8*10 and three norms of 8
    assert flops.param_count(HAND) == 656 + 80 + 24
    # seq 4: query i sees i+1 keys, 10 pairs a head; QK^T and PV are
    # 2*4 FLOPs each a pair; 2 heads -> 320 a sequence, 80 a token
    assert flops.attention_flops_per_token(HAND, 4) == 80
    assert flops.forward_flops_per_token(HAND, 4) == 2 * 656 + 80
    assert flops.train_flops_per_token(HAND, 4) == 3 * (2 * 656 + 80)
    assert flops.step_bytes(HAND) == 760 * 28


@pytest.mark.parametrize("cfg", [c["name"] for c in BENCH["configs"]])
def test_param_count_matches_program(cfg):
    from repro.models.model_factory import build_model
    import jax

    entry = next(c for c in BENCH["configs"] if c["name"] == cfg)
    config = json.loads((cells.ROOT / entry["file"]).read_text())
    arch = harness.make_arch(config)
    m = config["model"]
    v, e = m["vocab_size"], m["d_model"]
    # ArchConfig.param_count leaves out the final norm's scale
    assert flops.param_count(m) == arch.param_count() + e
    # the program's arrays pad the vocabulary to a multiple of 256
    shapes = jax.eval_shape(build_model(arch).init, jax.random.PRNGKey(0))
    total = sum(x.size for x in jax.tree.leaves(shapes))
    vp = -(-v // 256) * 256
    assert total == flops.param_count(m) + 2 * (vp - v) * e


def _config(name):
    return json.loads((cells.HERE / "configs" / f"{name}.json").read_text())


def test_published_cells_per_token():
    ds, yi = _config("ds7b-2l")["model"], _config("yi9b-2l")["model"]
    assert flops.param_count(ds) == 509_628_416
    assert flops.param_count(yi) == 411_586_560
    assert flops.train_flops_per_token(ds, 256) == pytest.approx(2.756e9, rel=1e-3)
    assert flops.train_flops_per_token(yi, 1024) == pytest.approx(2.323e9, rel=1e-3)
    # exactly what the counts gave before configurations named their
    # work module
    assert flops.train_flops_per_token(ds, 256) == 2755706880.0
    assert flops.train_flops_per_token(yi, 1024) == 2323169280.0
    assert flops.step_bytes(ds) == 14269595648.0
    assert flops.step_bytes(yi) == 11524423680.0


@pytest.mark.parametrize("cfg", ["ds7b-2l", "yi9b-2l"])
def test_dense_configurations_name_flops(cfg):
    assert cells.module(_config(cfg)["work"]) is flops


# (config, seq_len, capacity rows, live rows) -> mfu, step_mfu,
# step_roofline on one synthetic window, as the readers read them before
# they took the counts through the work module: bit for bit
PINNED = [
    (("ds7b-2l", 256, 12, 12),
     (42.68383699224202, 42.39927807896041, 44.78796980171874)),
    (("ds7b-2l", 256, 12, 6),
     (21.34191849612101, 21.199639039480203, 22.39398490085937)),
    (("yi9b-2l", 1024, 6, 6),
     (71.96830662403707, 71.48851791321015, 75.51604004916564)),
]


@pytest.mark.parametrize("cell,values", PINNED, ids=lambda x: str(x))
def test_readers_through_the_work_module_are_unchanged(cell, values):
    name, seq, rows, live = cell
    config = _config(name)
    steps = [harness.Step(0.1, live, rows, 0.0, 0.0, 1.0)] * 370
    ctx = harness.Context(steps, 37.25, 15.0, seq, config["model"],
                          cells.module(config["work"]),
                          harness.peaks_for("TPU v5 lite"), 1,
                          trace={"busy_s": 35.5, "window_s": 37.5,
                                 "n_steps": 370})
    got = tuple(cells.reader(k)(ctx) for k in ("mfu", "step_mfu",
                                               "step_roofline"))
    assert got == values
    assert ctx.least_step_seconds(live * seq)[1] == "compute"


def test_unknown_device_kind_raises():
    assert harness.peaks_for("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(harness.NoChip):
        harness.peaks_for("TPU v9 imaginary")


def test_peaks_carry_a_source():
    for kind, row in json.loads(harness.PEAKS.read_text()).items():
        assert row["source"], kind
