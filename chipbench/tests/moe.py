"""A configuration whose program is not a dense decoder, for CPU tests:
the registry's ``moonshot-v1-16b-a3b`` (top-k routed experts) cut to
tiny widths, with this module as its work module (``"work":
"tests.moe"``). Nothing under ``chipbench/`` knows of it: it reaches the
harness as a later configuration file would.

The work counts are ``flops.py``'s for a dense decoder, with each
layer's feed-forward block the router (d_model x experts) and the
``top_k`` experts a token is routed to; bytes over every expert's
parameters.
"""
from __future__ import annotations

import json
from typing import Any, Dict

from chipbench import cells, flops

Model = Dict[str, Any]

SIZES = {"num_layers": 2, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
         "head_dim": 16, "d_ff": 64, "vocab_size": 256}
EXPERTS = {"num_experts": 4, "top_k": 2, "expert_d_ff": 32}
SEQ_LEN = 32


def config() -> Dict[str, Any]:
    model = dict(SIZES, family="moe", activation="swiglu", norm_eps=1e-5,
                 rope_theta=10000.0, tie_embeddings=False, qkv_bias=False,
                 sliding_window=0, compute_dtype="bfloat16",
                 param_dtype="float32", init_std=0.02,
                 moe=dict(EXPERTS, capacity_factor=1.25))
    dense = json.loads((cells.HERE / "configs" / "ds7b-2l.json").read_text())
    return {"name": "moonshot-tiny", "arch": "moonshot-v1-16b-a3b",
            "overrides": dict(SIZES, moe=dict(EXPERTS)), "model": model,
            "optimizer": dense["optimizer"], "work": "tests.moe"}


def cell() -> cells.Cell:
    """The configuration under the steady mix at ``SEQ_LEN``, with the
    benchmark's metrics."""
    bench = json.loads(cells.BENCHMARK.read_text())
    mix = json.loads((cells.HERE / "traffic" / "s256.steady.json").read_text())
    mix["seq_len"] = SEQ_LEN
    return cells.Cell("moonshot-tiny.s32.steady", 1, config(), mix, {},
                      bench["end_to_end"], bench["per_layer"])


def _attention(m: Model) -> int:
    e, hd = m["d_model"], m["head_dim"]
    return e * m["num_heads"] * hd * 2 + e * m["num_kv_heads"] * hd * 2


def train_flops_per_token(m: Model, seq_len: int) -> float:
    x, e = m["moe"], m["d_model"]
    layer = (_attention(m) + e * x["num_experts"]
             + x["top_k"] * 3 * e * x["expert_d_ff"])
    matmul = m["num_layers"] * layer + e * m["vocab_size"]
    return 3.0 * (2.0 * matmul + flops.attention_flops_per_token(m, seq_len))


def step_bytes(m: Model) -> float:
    x, e = m["moe"], m["d_model"]
    layer = (_attention(m) + e * x["num_experts"]
             + x["num_experts"] * 3 * e * x["expert_d_ff"] + 2 * e)
    embed = 0 if m["tie_embeddings"] else e * m["vocab_size"]
    params = m["num_layers"] * layer + e * m["vocab_size"] + embed + e
    return float(params) * (flops.ADAMW_BYTES_PER_PARAM
                            + flops.WEIGHT_READ_BYTES_PER_PARAM)
