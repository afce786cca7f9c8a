"""The work a training step of a dense decoder requires, from shapes alone.

The work module of the dense configurations (their files' ``"work":
"flops"``): ``train_flops_per_token`` and ``step_bytes`` are what the
harness reads (``harness.Context``). ``m`` is a configuration file's
``model`` block. Counts are of the work the mathematics requires,
whatever implements it:

  * FLOPs: the matrix products of the forward pass (2 per multiply-add),
    attention under its causal mask (query i attends to keys 0..i), the
    LM head over the vocabulary slice; the backward pass twice the
    forward. Recomputation (remat), padding of the vocabulary, masked
    rows, norms and element-wise work are not counted.
  * Bytes: a lower bound on HBM traffic per step. AdamW reads and writes
    the f32 parameters and both f32 moments (24 bytes a parameter), and
    the weights reach the forward pass once more (4 bytes a parameter:
    f32 read, or a bf16 copy written and read). Activations are left
    out, so the bound stays a bound.
"""
from __future__ import annotations

from typing import Any, Dict

Model = Dict[str, Any]

ADAMW_BYTES_PER_PARAM = 24
WEIGHT_READ_BYTES_PER_PARAM = 4


def _ff_mats(m: Model) -> int:
    return 3 if m["activation"] == "swiglu" else 2


def layer_matmul_params(m: Model) -> int:
    e, hd = m["d_model"], m["head_dim"]
    attn = e * m["num_heads"] * hd * 2 + e * m["num_kv_heads"] * hd * 2
    return attn + _ff_mats(m) * e * m["d_ff"]


def matmul_params(m: Model) -> int:
    """Weights a token's forward pass multiplies by (the LM head too)."""
    return m["num_layers"] * layer_matmul_params(m) + m["d_model"] * m["vocab_size"]


def param_count(m: Model) -> int:
    """All parameters: matmul weights, the embedding table, the norms."""
    e = m["d_model"]
    norms = (2 * m["num_layers"] + 1) * e
    embed = 0 if m["tie_embeddings"] else e * m["vocab_size"]
    return matmul_params(m) + embed + norms


def attention_flops_per_token(m: Model, seq_len: int) -> float:
    """Causal QK^T and PV, averaged over the sequence: query i sees i + 1
    keys, so a sequence costs 4 * H * D * S(S+1)/2 per layer."""
    return m["num_layers"] * 2.0 * m["num_heads"] * m["head_dim"] * (seq_len + 1)


def forward_flops_per_token(m: Model, seq_len: int) -> float:
    return 2.0 * matmul_params(m) + attention_flops_per_token(m, seq_len)


def train_flops_per_token(m: Model, seq_len: int) -> float:
    """Forward + backward (twice the forward), without recomputation."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def step_bytes(m: Model) -> float:
    """Least HBM bytes one optimizer step moves, whatever the batch."""
    return float(param_count(m)) * (ADAMW_BYTES_PER_PARAM
                                    + WEIGHT_READ_BYTES_PER_PARAM)
