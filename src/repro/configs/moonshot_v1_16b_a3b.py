"""moonshot-v1-16b-a3b — a generic softmax-routed expert stand-in at
Moonlight's widths (hidden 2048, 16 heads of 128, 64 experts of 1408,
top-6), not the published block: 48 layers of plain multi-head attention
and a softmax router with capacity drops, no shared experts and no
leading dense layer. Moonlight-16B-A3B itself is ``moonlight-16b-a3b``.
"""
from repro.configs.base import ArchConfig, MoEConfig, register_arch

MOONSHOT_V1_16B_A3B = register_arch(ArchConfig(
    name="moonshot-v1-16b-a3b",
    family="moe",
    num_layers=48,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=1408,
    vocab_size=163840,
    moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408),
    source="generic softmax-routed expert stand-in at Moonlight's widths; "
           "the published block is moonlight-16b-a3b",
))
