"""moonlight-16b-a3b — Moonlight-16B-A3B (DeepSeek-V3 block).

27 layers of hidden size 2048 with multi-head latent attention (16 heads,
no q compression, KV latent 512, q/k heads of 128 + 64 rotary, v heads
of 128); layer 0 has a dense SwiGLU MLP of 11264, the other 26 have 64
routed experts of 1408 (top-6 by sigmoid scores with a bias-corrected,
normalised top-k scaled by 2.446; one expert group) beside 2 shared
experts. Vocabulary 163,840, untied; RoPE theta 50000, RMSNorm eps 1e-5,
8192 positions. The sequence-wise balance weight and the bias update
speed are not in the published config: DeepSeek-V3's (arXiv:2412.19437)
1e-4 and 1e-3.

[hf:moonshotai/Moonlight-16B-A3B config.json]
"""
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, register_arch

MOONLIGHT_16B_A3B = register_arch(ArchConfig(
    name="moonlight-16b-a3b",
    family="moe",
    num_layers=27,
    d_model=2048,
    num_heads=16,
    num_kv_heads=16,
    d_ff=11264,
    vocab_size=163840,
    norm_eps=1e-5,
    rope_theta=50000.0,
    mla=MLAConfig(kv_lora_rank=512, qk_nope_head_dim=128,
                  qk_rope_head_dim=64, v_head_dim=128),
    moe=MoEConfig(num_experts=64, top_k=6, expert_d_ff=1408,
                  router="noaux_tc", shared_experts=2, routed_scale=2.446,
                  seq_aux_weight=1e-4, bias_update_speed=1e-3,
                  dense_prefix=1),
    source="https://huggingface.co/moonshotai/Moonlight-16B-A3B/blob/main/"
           "config.json",
))
