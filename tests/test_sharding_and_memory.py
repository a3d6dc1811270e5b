"""Sharding rules + memory/time models."""
import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import configs
from repro.core.memory_model import (arch_active_param_count, arch_param_count,
                                     full_model_flops, model_flops_6nd,
                                     stage_flops, stage_memory_bytes,
                                     full_model_memory_bytes)
from repro.core.time_model import stage_speedup
from repro.dist.sharding import logical_to_spec, make_rules


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape


def test_rules_llama_heads_sharded():
    mesh = FakeMesh({"data": 16, "model": 16})
    r = make_rules(configs.get("llama3-8b"), mesh)
    assert r["heads"] == "model" and r["qkv_in"] is None
    assert r["vocab"] == "model"


def test_rules_minicpm3_fallback():
    mesh = FakeMesh({"data": 16, "model": 16})
    r = make_rules(configs.get("minicpm3-4b"), mesh)
    assert r["heads"] is None and r["qkv_in"] == "model"  # 40 heads % 16 != 0
    assert r["vocab"] is None and r["embed"] == "model"  # 73448 % 16 != 0


def test_rules_moe_sharding_modes():
    mesh = FakeMesh({"data": 16, "model": 16})
    r_ds = make_rules(configs.get("deepseek-v2-236b"), mesh)
    assert r_ds["expert"] == "model"  # EP: 160 / 16
    r_gk = make_rules(configs.get("grok-1-314b"), mesh)
    assert r_gk["expert"] is None and r_gk["moe_ff"] == "model"  # expert-TP


def test_logical_to_spec_no_axis_reuse():
    rules = {"a": "model", "b": "model"}
    spec = logical_to_spec(("a", "b"), rules, (32, 32))
    assert spec == P("model", None)  # axis used once


def test_param_counts_match_public_numbers():
    # within 10% of the published total param counts
    expect = {"llama3-8b": 8.0e9, "qwen2-72b": 72e9, "deepseek-v2-236b": 236e9,
              "grok-1-314b": 314e9, "deepseek-coder-33b": 33e9,
              "granite-4.0-h-micro": 3.0e9}
    for name, n in expect.items():
        got = arch_param_count(configs.get(name))
        assert abs(got - n) / n < 0.12, (name, got, n)


def test_moe_active_params():
    cfg = configs.get("deepseek-v2-236b")
    active = arch_active_param_count(cfg)
    total = arch_param_count(cfg)
    assert active < 0.2 * total  # ~21B active of 236B
    assert abs(active - 21e9) / 21e9 < 0.3


def test_stage_memory_reduction_magnitude():
    """Paper claims up to 82% average memory reduction — early stages of a
    deep model should show large savings vs full training."""
    cfg = configs.get("llama3-8b")
    full = full_model_memory_bytes(cfg, batch=8, seq=4096)["total"]
    st0 = stage_memory_bytes(cfg, 0, batch=8, seq=4096)["total"]
    assert st0 < 0.5 * full


def test_hybrid_stage_memory_and_flops():
    """Granite's Mamba-2 + MLP layers in the memory and time models: as
    for a transformer, stage 0 holds well under full training and runs
    cheaper rounds."""
    cfg = configs.get("granite-4.0-h-micro")
    full = full_model_memory_bytes(cfg, batch=8, seq=4096)["total"]
    assert stage_memory_bytes(cfg, 0, batch=8, seq=4096)["total"] < 0.5 * full
    assert stage_speedup(cfg, 0, batch=1, seq=4096) > 1.5


def test_stage_flops_speedup():
    cfg = configs.get("llama3-8b")
    sp = stage_speedup(cfg, 0, batch=1, seq=4096)
    assert sp > 1.5  # early-stage rounds much cheaper than full training


def test_model_flops_6nd():
    cfg = configs.get("llama3-8b")
    mf = model_flops_6nd(cfg, 256, 4096)
    assert abs(mf - 6 * arch_param_count(cfg) * 256 * 4096) < 1e-3 * mf
