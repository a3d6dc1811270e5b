"""Granite 4.0-H Micro (ibm-granite/granite-4.0-h-micro, config.json;
``model_type`` granitemoehybrid, dense): 40 decoder layers at hidden size
2048, layers 5/15/25/35 NoPE GQA attention (32 query heads, 8 KV heads of
64, softmax scale ``attention_multiplier``), the other 36 Mamba-2 mixers
(64 heads of 64, state 128, one group, causal conv 4 with bias, chunk 256,
gated RMSNorm over d_inner); every mixer is followed by a SwiGLU MLP of
width ``shared_intermediate_size``. Embedding times 12, each residual
branch times 0.22, logits over 8, tied embeddings.

``from_hf`` reads such a config.json (the benchmark's configuration file
holds one, cut in depth and vocabulary) into an ``ArchConfig``."""
from typing import Dict

from repro.configs.base import ArchConfig, register

# config.json as published (the keys that give the model's shape)
PUBLISHED: Dict = {
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192,
    "layer_types": ["mamba"] * 5 + ["attention"] + (["mamba"] * 9
                                                   + ["attention"]) * 3
    + ["mamba"] * 4,
    "logits_scaling": 8, "mamba_chunk_size": 256, "mamba_conv_bias": True,
    "mamba_d_conv": 4, "mamba_d_head": 64, "mamba_d_state": 128,
    "mamba_expand": 2, "mamba_n_groups": 1, "mamba_n_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 131072,
    "model_type": "granitemoehybrid", "normalization_function": "rmsnorm",
    "num_attention_heads": 32, "num_experts_per_tok": 0,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


def from_hf(hf: Dict, **overrides) -> ArchConfig:
    """An ``ArchConfig`` from a granitemoehybrid config.json (dense: no
    experts, one Mamba group, no biases but the conv's)."""
    d, heads = hf["hidden_size"], hf["num_attention_heads"]
    if (hf["num_local_experts"] or hf["mamba_n_groups"] != 1
            or hf["attention_bias"] or hf["mamba_proj_bias"]
            or not hf["mamba_conv_bias"]
            or hf["mamba_n_heads"] * hf["mamba_d_head"]
            != hf["mamba_expand"] * d):
        raise ValueError("from_hf reads the dense one-group layout only")
    fields = dict(
        name="granite-4.0-h-micro", family="hybrid",
        num_layers=hf["num_hidden_layers"], d_model=d, num_heads=heads,
        num_kv_heads=hf["num_key_value_heads"],
        d_ff=hf["shared_intermediate_size"], vocab_size=hf["vocab_size"],
        head_dim=d // heads,
        position_embedding={"nope": "none", "rope": "rope"}[
            hf["position_embedding_type"]],
        attention_multiplier=hf["attention_multiplier"],
        rope_theta=float(hf["rope_theta"]), qkv_bias=hf["attention_bias"],
        ssm_state=hf["mamba_d_state"], ssm_head_dim=hf["mamba_d_head"],
        ssm_expand=hf["mamba_expand"], conv_kernel=hf["mamba_d_conv"],
        ssm_chunk=hf["mamba_chunk_size"],
        layer_types=tuple(hf["layer_types"]),
        mlp_activation=hf["hidden_act"], norm="rmsnorm",
        norm_eps=hf["rms_norm_eps"],
        tie_embeddings=hf["tie_word_embeddings"],
        embedding_multiplier=float(hf["embedding_multiplier"]),
        residual_multiplier=float(hf["residual_multiplier"]),
        logits_scaling=float(hf["logits_scaling"]),
        subquadratic=True, num_freeze_blocks=5)
    fields.update(overrides)
    return ArchConfig(**fields)


CONFIG = register(from_hf(PUBLISHED))
