"""Config system: architecture + shape + mesh + run configs.

Every assigned architecture registers an :class:`ArchConfig` via
``register_arch``; shapes are global (``SHAPES``) and each arch declares
which shapes apply to it (``long_500k`` only for sub-quadratic families).
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Dict, Optional, Sequence, Tuple

# ---------------------------------------------------------------------------
# Architecture config
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    num_experts: int
    top_k: int
    expert_d_ff: int
    # capacity factor for dispatch buffers (tokens per expert = tokens/E * cf)
    capacity_factor: float = 1.25
    router_jitter: float = 0.0
    # aux load-balance loss weight (switch-transformer style)
    aux_loss_weight: float = 0.01
    # "softmax": softmax top-k with capacity drops (models/moe.py);
    # "noaux_tc": DeepSeek-V3's sigmoid scores, top-k of score + a routing
    # bias kept by a sign rule, the chosen scores normalised and scaled by
    # ``routed_scale``; dropless, over the experts held (models/moe.py)
    router: str = "softmax"
    shared_experts: int = 0       # SwiGLU of shared_experts * expert_d_ff
    routed_scale: float = 1.0
    seq_aux_weight: float = 0.0   # alpha of the sequence-wise balance loss
    bias_update_speed: float = 0.0  # gamma of the routing bias's sign rule
    # this device's share under expert parallelism: experts
    # [held_offset, held_offset + experts_held) of num_experts (0 = all)
    experts_held: int = 0
    held_offset: int = 0
    dense_prefix: int = 0         # leading layers with a dense d_ff MLP

    @property
    def held(self) -> int:
        return self.experts_held or self.num_experts


@dataclasses.dataclass(frozen=True)
class MLAConfig:
    """Multi-head latent attention (DeepSeek-V2/V3): keys and values
    decompressed from a normed latent, plus one rotary key shared by all
    heads; q/k heads of nope + rope, v heads of their own width."""
    kv_lora_rank: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    def params(self, d_model: int, heads: int) -> int:
        return (d_model * heads * self.qk_head_dim
                + d_model * (self.kv_lora_rank + self.qk_rope_head_dim)
                + self.kv_lora_rank
                + self.kv_lora_rank * heads * (self.qk_nope_head_dim
                                               + self.v_head_dim)
                + heads * self.v_head_dim * d_model)


@dataclasses.dataclass(frozen=True)
class SSMConfig:
    state_dim: int          # N, per-head SSM state size
    head_dim: int = 64      # P, channels per SSD head
    chunk_size: int = 256   # SSD block length
    conv_width: int = 4     # depthwise causal conv width
    expand: int = 2         # d_inner = expand * d_model


@dataclasses.dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str                   # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int                # query heads (0 for attention-free)
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0             # 0 -> d_model // num_heads
    qkv_bias: bool = False
    tie_embeddings: bool = False
    norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    sliding_window: int = 0       # 0 = full attention
    activation: str = "swiglu"    # swiglu | gelu
    moe: Optional[MoEConfig] = None
    mla: Optional[MLAConfig] = None
    ssm: Optional[SSMConfig] = None
    # hybrid (zamba2): attention block shared across layers, applied every k
    hybrid_attn_every: int = 0    # 0 = no interleaved attention
    # vlm: cross-attention to image embeddings every k layers
    cross_attn_every: int = 0
    num_image_tokens: int = 0     # stub frontend: precomputed patch embeds
    # audio (whisper): encoder-decoder
    is_encoder_decoder: bool = False
    encoder_layers: int = 0
    max_encoder_len: int = 1500   # whisper frame positions (stub frontend)
    dtype: str = "bfloat16"
    # which shapes apply (dry-run matrix); None = all four
    shapes: Optional[Tuple[str, ...]] = None
    source: str = ""

    @property
    def resolved_head_dim(self) -> int:
        if self.head_dim:
            return self.head_dim
        if self.num_heads:
            return self.d_model // self.num_heads
        return 0

    @property
    def is_subquadratic(self) -> bool:
        return self.family in ("ssm", "hybrid") or self.sliding_window > 0

    def applicable_shapes(self) -> Tuple[str, ...]:
        if self.shapes is not None:
            return self.shapes
        names = ["train_4k", "prefill_32k", "decode_32k"]
        if self.is_subquadratic:
            names.append("long_500k")
        return tuple(names)

    def param_count(self) -> int:
        """Analytic parameter count (total; for MoE includes all experts)."""
        E, L, V = self.d_model, self.num_layers, self.vocab_size
        h = self.resolved_head_dim
        p = V * E  # embedding
        if not self.tie_embeddings:
            p += V * E
        per_layer = 0
        if self.family == "ssm":
            per_layer = _ssm_layer_params(self)
        else:
            # attention
            nq, nkv = self.num_heads, self.num_kv_heads
            if self.mla is not None:
                attn = self.mla.params(E, nq)
            else:
                attn = E * nq * h + 2 * E * nkv * h + nq * h * E
            if self.qkv_bias:
                attn += (nq + 2 * nkv) * h
            if self.family == "hybrid":
                # mamba2 backbone layers + one shared attn+MLP block
                per_layer = _ssm_layer_params(self)
                ff_shared = (3 if self.activation == "swiglu" else 2) \
                    * E * self.d_ff
                p += attn + ff_shared + 4 * E  # shared block + 2 norms
            else:
                per_layer = attn
            if self.moe is not None:
                fe, m = self.moe.expert_d_ff, self.moe
                ff = m.held * (3 * E * fe) + E * m.num_experts
                if m.router == "noaux_tc":
                    ff += m.num_experts  # the routing bias
                ff += 3 * E * fe * m.shared_experts
                # the leading dense layers: their MLP in place of experts
                p += m.dense_prefix * (3 * E * self.d_ff - ff)
            elif self.family == "hybrid":
                ff = 0
            elif self.activation == "swiglu":
                ff = 3 * E * self.d_ff
            else:
                ff = 2 * E * self.d_ff
            per_layer += ff
        per_layer += 2 * E  # norms
        p += L * per_layer
        if self.cross_attn_every:
            n_cross = self.num_layers // self.cross_attn_every
            nq, nkv = self.num_heads, self.num_kv_heads
            p += n_cross * (E * nq * h + 2 * E * nkv * h + nq * h * E + 2 * E)
        if self.is_encoder_decoder:
            # encoder self-attn + mlp, and decoder cross-attn already above
            nq, nkv = self.num_heads, self.num_kv_heads
            attn = E * nq * h + 2 * E * nkv * h + nq * h * E
            ffp = 2 * E * self.d_ff if self.activation == "gelu" else 3 * E * self.d_ff
            p += self.encoder_layers * (attn + ffp + 2 * E)
            p += self.num_layers * (attn + 2 * E)  # decoder cross-attn blocks
        return p

    def active_param_count(self) -> int:
        """Params touched per token (MoE: only top-k experts)."""
        if self.moe is None:
            return self.param_count()
        E = self.d_model
        fe = self.moe.expert_d_ff
        total = self.param_count()
        layers = self.num_layers - self.moe.dense_prefix
        held = layers * self.moe.held * 3 * E * fe
        # a token's top_k experts, of which the held share lies here
        active = layers * 3 * E * fe * min(
            self.moe.top_k * self.moe.held / self.moe.num_experts,
            self.moe.held)
        return int(total - held + active)


def _ssm_layer_params(cfg: ArchConfig) -> int:
    s = cfg.ssm
    assert s is not None
    E = cfg.d_model
    d_inner = s.expand * E
    nheads = d_inner // s.head_dim
    # in_proj -> [z, x, B, C, dt]
    proj_in = E * (2 * d_inner + 2 * s.state_dim + nheads)
    conv = s.conv_width * (d_inner + 2 * s.state_dim)
    out = d_inner * E
    extra = 2 * nheads + d_inner  # A_log, dt_bias, norm gate
    return proj_in + conv + out + extra


# ---------------------------------------------------------------------------
# Shape configs
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: Dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524288, 1, "decode"),
}


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_ARCHS: Dict[str, ArchConfig] = {}

_ARCH_MODULES = [
    "zamba2_1p2b", "codeqwen1p5_7b", "yi_9b", "qwen1p5_4b", "deepseek_7b",
    "llama32_vision_11b", "mamba2_1p3b", "whisper_tiny", "mixtral_8x7b",
    "moonshot_v1_16b_a3b", "moonlight_16b_a3b",
]


def register_arch(cfg: ArchConfig) -> ArchConfig:
    _ARCHS[cfg.name] = cfg
    return cfg


def get_arch(name: str) -> ArchConfig:
    load_all_archs()
    if name not in _ARCHS:
        raise KeyError(f"unknown arch {name!r}; known: {sorted(_ARCHS)}")
    return _ARCHS[name]


def list_archs() -> Sequence[str]:
    load_all_archs()
    return sorted(_ARCHS)


def load_all_archs() -> None:
    for mod in _ARCH_MODULES:
        importlib.import_module(f"repro.configs.{mod}")


def reduced_config(cfg: ArchConfig, **overrides) -> ArchConfig:
    """A tiny same-family config for CPU smoke tests."""
    changes: Dict[str, object] = dict(
        num_layers=2,
        d_model=64,
        num_heads=4 if cfg.num_heads else 0,
        num_kv_heads=min(cfg.num_kv_heads, 2) if cfg.num_heads else 0,
        d_ff=128 if cfg.d_ff else 0,
        vocab_size=256,
        head_dim=16 if cfg.num_heads else 0,
        sliding_window=min(cfg.sliding_window, 64) if cfg.sliding_window else 0,
        num_image_tokens=16 if cfg.num_image_tokens else 0,
        max_encoder_len=32 if cfg.is_encoder_decoder else cfg.max_encoder_len,
        encoder_layers=2 if cfg.is_encoder_decoder else 0,
        dtype="float32",
    )
    if cfg.moe is not None:
        # every mechanism kept (router kind, shared experts, the dense
        # prefix, a held share where one is set), widths and counts cut
        n = 8 if cfg.moe.router == "noaux_tc" else 4
        held = min(cfg.moe.experts_held, n // 2)
        changes["moe"] = dataclasses.replace(
            cfg.moe, num_experts=n, top_k=2, expert_d_ff=64,
            experts_held=held,
            held_offset=min(cfg.moe.held_offset, n - held) if held else 0,
            dense_prefix=min(cfg.moe.dense_prefix, 1))
    if cfg.mla is not None:
        changes["mla"] = MLAConfig(kv_lora_rank=32, qk_nope_head_dim=16,
                                   qk_rope_head_dim=8, v_head_dim=16)
    if cfg.ssm is not None:
        changes["ssm"] = SSMConfig(state_dim=16, head_dim=16, chunk_size=16,
                                   conv_width=cfg.ssm.conv_width, expand=2)
    if cfg.hybrid_attn_every:
        changes["hybrid_attn_every"] = 2
    if cfg.cross_attn_every:
        changes["cross_attn_every"] = 2
    changes.update(overrides)
    return dataclasses.replace(cfg, **changes)
