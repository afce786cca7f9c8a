"""The work a training step of a DeepSeek-V3-style expert stack requires,
from shapes alone: the work module of ``moonlight-5l`` (its file's
``"work": "moe_flops"``). ``m`` is a configuration file's ``model``
block: latent attention (``mla``), leading dense layers and expert layers
that hold ``experts_held`` of ``num_experts`` (``moe``). Counted as
``flops.py`` counts a dense decoder (2 FLOPs a multiply-add, the backward
twice the forward, no recomputation, masked rows and element-wise work
left out). Per token, forward:

  latent attention, each layer
      projections   2 * (E*H*(n+r) + E*(c+r) + c*H*(n+v) + H*v*E)
      causal core   H * (n + r + v) * (S + 1)       query i sees i + 1 keys
  dense layers      2 * 3*E*F_dense
  expert layers     2 * E*X                         the router, all X experts
                    2 * 3*E*(shared * F_e)          the shared experts
                    2 * 3*E*F_e * top_k*held/X      the held experts, at the
                                                    expected share of a
                                                    token's top_k
  LM head           2 * E*V                         over the vocabulary slice

with E d_model, H heads, n / r / v the nope, rope and v head widths, c
the KV latent, S the sequence, X the router's experts. Bytes are
``flops.py``'s 28 a parameter: AdamW's f32 parameters and two moments
read and written, one more weight read; the routing bias moves with the
parameters. ``gmm_flops`` and ``gmm_bytes`` count the held experts'
grouped products alone for a given number of routed rows, for their
roofline.
"""
from __future__ import annotations

from typing import Any, Dict

from chipbench import flops

Model = Dict[str, Any]


def mla_params(m: Model) -> int:
    a, e, h = m["mla"], m["d_model"], m["num_heads"]
    c, n, r, v = (a["kv_lora_rank"], a["qk_nope_head_dim"],
                  a["qk_rope_head_dim"], a["v_head_dim"])
    return e * h * (n + r) + e * (c + r) + c * h * (n + v) + h * v * e


def expert_params(m: Model) -> int:
    """One held expert's SwiGLU."""
    return 3 * m["d_model"] * m["moe"]["expert_d_ff"]


def _layers(m: Model):
    dense = m["moe"]["dense_prefix"]
    return dense, m["num_layers"] - dense


def forward_flops_per_token(m: Model, seq_len: int) -> float:
    x, e = m["moe"], m["d_model"]
    a = m["mla"]
    dense, routed = _layers(m)
    core = m["num_heads"] * (a["qk_nope_head_dim"] + a["qk_rope_head_dim"]
                             + a["v_head_dim"]) * (seq_len + 1)
    attention = m["num_layers"] * (2.0 * mla_params(m) + core)
    mlp = dense * 2.0 * 3 * e * m["d_ff"]
    experts = routed * 2.0 * (
        e * x["num_experts"] + x["shared_experts"] * expert_params(m)
        + expert_params(m) * x["top_k"] * x["experts_held"] / x["num_experts"])
    return attention + mlp + experts + 2.0 * e * m["vocab_size"]


def train_flops_per_token(m: Model, seq_len: int) -> float:
    """Forward + backward (twice the forward), without recomputation."""
    return 3.0 * forward_flops_per_token(m, seq_len)


def param_count(m: Model) -> int:
    """Every parameter the chip holds: embedding, head, norms, latent
    attention, the dense MLPs, and per expert layer the router, its bias,
    the held and the shared experts."""
    x, e = m["moe"], m["d_model"]
    dense, routed = _layers(m)
    norms = (2 * m["num_layers"] + 1) * e + m["num_layers"] * m["mla"]["kv_lora_rank"]
    embed = (1 if m["tie_embeddings"] else 2) * e * m["vocab_size"]
    layer = (e * x["num_experts"] + x["num_experts"]
             + (x["experts_held"] + x["shared_experts"]) * expert_params(m))
    return (embed + norms + m["num_layers"] * mla_params(m)
            + dense * 3 * e * m["d_ff"] + routed * layer)


def step_bytes(m: Model) -> float:
    """Least HBM bytes one optimizer step moves, whatever the batch."""
    return float(param_count(m)) * (flops.ADAMW_BYTES_PER_PARAM
                                    + flops.WEIGHT_READ_BYTES_PER_PARAM)


def gmm_flops(m: Model, rows: float) -> float:
    """The held experts' three grouped products, forward and backward, on
    ``rows`` routed token-slots (summed over the layers)."""
    return 3.0 * 2.0 * rows * expert_params(m)


def gmm_bytes(m: Model, rows: float) -> float:
    """Least HBM bytes of those products in bf16: each layer's held
    weights read, and every routed row's inputs and outputs of the three
    products (E in and F out twice, F in and E out) read or written, in
    the forward and twice in the backward."""
    e, f = m["d_model"], m["moe"]["expert_d_ff"]
    _, routed = _layers(m)
    weights = routed * m["moe"]["experts_held"] * expert_params(m)
    return 3.0 * 2.0 * (weights + rows * 3 * (e + f))
