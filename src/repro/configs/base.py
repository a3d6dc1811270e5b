"""Architecture + shape configuration system.

Every assigned architecture is a frozen ``ArchConfig``; every assigned input
shape is a ``ShapeConfig``. The registry maps ``--arch <id>`` to its config and
``reduced()`` derives the CPU-smoke-test variant of the same family.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Optional, Tuple

# ---------------------------------------------------------------------------
# Shape configs (assigned input-shape set; LM shapes are seq_len x batch)
# ---------------------------------------------------------------------------


@dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


TRAIN_4K = ShapeConfig("train_4k", 4096, 256, "train")
PREFILL_32K = ShapeConfig("prefill_32k", 32768, 32, "prefill")
DECODE_32K = ShapeConfig("decode_32k", 32768, 128, "decode")
LONG_500K = ShapeConfig("long_500k", 524288, 1, "decode")

SHAPES = {s.name: s for s in (TRAIN_4K, PREFILL_32K, DECODE_32K, LONG_500K)}


# ---------------------------------------------------------------------------
# Architecture configs
# ---------------------------------------------------------------------------

# published ``layer_types`` names -> the layer kinds of models/transformer.py
LAYER_TYPE_KINDS = {"mamba": "mamba2_mlp", "attention": "attn_mlp"}


@dataclass(frozen=True)
class ArchConfig:
    name: str
    family: str  # dense | moe | ssm | hybrid | vlm | audio
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    head_dim: int = 0  # 0 -> d_model // num_heads

    # --- attention flavour ---
    attention: str = "gqa"  # gqa | mla
    # full-sequence execution path: "xla" = dense einsum / blockwise online
    # softmax (models/attention.py); "pallas" = kernels/flash_attention.py
    # via kernels.ops (interpret-mode off-TPU). GQA only; opt-in via
    # launch/train.py --use-pallas.
    attention_impl: str = "xla"
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    position_embedding: str = "rope"  # rope | none (granite's NoPE layers)
    attention_multiplier: float = 0.0  # softmax scale; 0 -> 1/sqrt(head_dim)

    # --- MLA (deepseek-v2 / minicpm3) ---
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_dim: int = 0
    qk_rope_dim: int = 0
    v_head_dim: int = 0

    # --- MoE ---
    num_experts: int = 0
    num_shared_experts: int = 0
    experts_per_token: int = 0
    moe_d_ff: int = 0
    moe_impl: str = "gshard"  # gshard | a2a
    moe_sharding: str = "ep"  # ep (expert-parallel) | tp (expert tensor-parallel)
    capacity_factor: float = 1.25
    first_dense_layers: int = 0  # leading layers that use the dense MLP

    # --- SSM / recurrent ---
    ssm_state: int = 0
    ssm_head_dim: int = 64
    ssm_expand: int = 2
    conv_kernel: int = 4
    ssm_chunk: int = 256  # SSD chunk length (Mamba-2's chunk_size)
    slstm_every: int = 0  # xlstm: every k-th layer is sLSTM (0 = none)

    # --- hybrid (zamba2) ---
    attn_every: int = 0  # every k-th layer is a (shared) attention layer
    num_shared_attn_sets: int = 0  # weight-tied attention block sets
    # --- explicit layer pattern (granite-4.0-h): the published
    # ``layer_types`` ("mamba" | "attention"), read for the first num_layers;
    # each mixer is followed by the MLP ---
    layer_types: tuple = ()

    # --- encoder-only / modality ---
    is_encoder_only: bool = False
    modality: str = "text"  # text | vision_stub | audio_stub
    frontend_dim: int = 0  # stub feature dim (vision/audio)
    num_image_tokens: int = 0  # vlm: vision tokens prepended per sequence

    # --- activation / misc ---
    mlp_activation: str = "silu"  # silu | gelu
    norm: str = "rmsnorm"  # rmsnorm | layernorm
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # granite's scalings: h = embedding_multiplier * E[tokens]; each residual
    # branch times residual_multiplier; logits divided by logits_scaling
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0

    # --- SmartFreeze / progressive training ---
    num_freeze_blocks: int = 4

    # --- numerics ---
    param_dtype: str = "bfloat16"
    compute_dtype: str = "bfloat16"

    # --- distribution context (threaded by the launcher; ("data",) inside the
    # federated vmap-over-pods where the pod axis is already consumed) ---
    batch_axes: tuple = ("pod", "data")

    # --- capability flags ---
    subquadratic: bool = False  # True for SSM/hybrid: long_500k is runnable

    def __post_init__(self):
        if self.head_dim == 0:
            object.__setattr__(self, "head_dim", self.d_model // max(self.num_heads, 1))

    # ----- derived properties -----

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def q_heads_per_kv(self) -> int:
        return self.num_heads // max(self.num_kv_heads, 1)

    def layer_kinds(self) -> Tuple[str, ...]:
        """Per-layer kind string, length num_layers."""
        if self.layer_types:
            return tuple(LAYER_TYPE_KINDS[t]
                         for t in self.layer_types[:self.num_layers])
        kinds = []
        for i in range(self.num_layers):
            if self.family == "ssm":  # xlstm
                if self.slstm_every and (i % self.slstm_every) == (self.slstm_every - 1):
                    kinds.append("slstm")
                else:
                    kinds.append("mlstm")
            elif self.family == "hybrid":  # zamba2
                if self.attn_every and (i % self.attn_every) == (self.attn_every - 1):
                    kinds.append("shared_attn")
                else:
                    kinds.append("mamba2")
            elif self.is_moe:
                if i < self.first_dense_layers:
                    kinds.append("attn_mlp")
                else:
                    kinds.append("attn_moe")
            else:
                kinds.append("attn_mlp")
        return tuple(kinds)

    def segments(self) -> Tuple[Tuple[str, int], ...]:
        """Contiguous homogeneous (kind, count) runs — each run is one scan."""
        kinds = self.layer_kinds()
        segs = []
        for k in kinds:
            if segs and segs[-1][0] == k:
                segs[-1][1] += 1
            else:
                segs.append([k, 1])
        return tuple((k, n) for k, n in segs)

    def block_boundaries(self) -> Tuple[int, ...]:
        """Layer-index boundaries of the num_freeze_blocks SmartFreeze blocks.

        Returns (b_0=0, b_1, ..., b_T=num_layers): block t spans
        [boundaries[t], boundaries[t+1]).
        """
        T = self.num_freeze_blocks
        L = self.num_layers
        base, rem = divmod(L, T)
        sizes = [base + (1 if i < rem else 0) for i in range(T)]
        bounds = [0]
        for s in sizes:
            bounds.append(bounds[-1] + s)
        return tuple(bounds)

    def param_count(self) -> int:
        """Analytic parameter count (for 6ND MODEL_FLOPS and memory model)."""
        from repro.core.memory_model import arch_param_count

        return arch_param_count(self)

    def active_param_count(self) -> int:
        from repro.core.memory_model import arch_active_param_count

        return arch_active_param_count(self)

    def reduced(self, **overrides) -> "ArchConfig":
        """A tiny same-family config for CPU smoke tests."""
        small = dict(
            num_layers=max(4, min(self.num_layers, 4)),
            d_model=64,
            num_heads=4,
            num_kv_heads=min(self.num_kv_heads, 4) if self.num_kv_heads else 4,
            d_ff=128 if self.d_ff else 0,
            vocab_size=256,
            head_dim=16,
        )
        if self.attention == "mla":
            small.update(q_lora_rank=32 if self.q_lora_rank else 0, kv_lora_rank=32,
                         qk_nope_dim=16, qk_rope_dim=8, v_head_dim=16)
        if self.is_moe:
            small.update(num_experts=4, experts_per_token=2, moe_d_ff=64,
                         num_shared_experts=min(self.num_shared_experts, 1),
                         first_dense_layers=min(self.first_dense_layers, 1))
        if self.ssm_state:
            small.update(ssm_state=16, ssm_head_dim=16)
        if self.attn_every:
            small.update(attn_every=2)
        if self.layer_types:  # through the first attention layer
            small.update(num_layers=self.layer_types.index("attention") + 1)
        if self.slstm_every:
            small.update(slstm_every=4)
        if self.modality == "vision_stub":
            small.update(frontend_dim=32, num_image_tokens=8)
        if self.modality == "audio_stub":
            small.update(frontend_dim=32)
        small.update(num_freeze_blocks=min(self.num_freeze_blocks, 2),
                     name=self.name + "-reduced")
        small.update(overrides)
        return dataclasses.replace(self, **small)


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------

_REGISTRY: dict = {}


def register(cfg: ArchConfig) -> ArchConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get(name: str) -> ArchConfig:
    if name not in _REGISTRY:
        _load_all()
    return _REGISTRY[name]


def names() -> list:
    _load_all()
    return sorted(_REGISTRY.keys())


_LOADED = False


def _load_all():
    global _LOADED
    if _LOADED:
        return
    _LOADED = True
    # import every config module for its registration side effect
    from repro.configs import (  # noqa: F401
        xlstm_350m, deepseek_v2_236b, grok1_314b, minicpm3_4b, llama3_8b,
        qwen2_72b, deepseek_coder_33b, internvl2_2b, hubert_xlarge, zamba2_7b,
        granite_4_0_h_micro, resnet_cifar, vgg_cifar,
    )


def shapes_for(cfg: ArchConfig) -> list:
    """The assigned shapes this arch actually runs (skips per DESIGN.md §5)."""
    out = [TRAIN_4K, PREFILL_32K]
    if not cfg.is_encoder_only:
        out.append(DECODE_32K)
        if cfg.subquadratic:
            out.append(LONG_500K)
    return out


def skip_reason(cfg: ArchConfig, shape: ShapeConfig) -> Optional[str]:
    if cfg.is_encoder_only and shape.kind == "decode":
        return "encoder-only arch: no decode step"
    if shape.name == "long_500k" and not cfg.subquadratic:
        return "pure full-attention arch: long_500k skipped per assignment"
    return None
