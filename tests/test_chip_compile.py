"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler is installed with JAX, so these tests lower and compile
for a described ``v5e:2x2`` topology: the Pallas cohort fold at ResNet-18
leaf sizes, the one-chip ResNet-18 fused round (stage 0 and a cached
stage), and the round shard_mapped over a 4-device client mesh. They catch
what the chip's compiler refuses (block shapes, scoped VMEM, memory) at no
chip time; they run nothing, so they say nothing about results or times.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library, and the worker that runs
this file keeps it until it exits.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import (NamedSharding, PartitionSpec as P,
                          SingleDeviceSharding)

from repro.core import freezing_cnn as fz
from repro.fl.compression import topk_keep
from repro.fl.engine import make_fused_round
from repro.fl.quant import make_tiered_loss
from repro.kernels import dequant_matmul, flash_attention, ops, sparse_agg
from repro.launch.mesh import make_client_mesh
from repro.models.cnn import CNN, RESNET18
from repro.optim import sgd

K, NB, B, IMG = 8, 2, 32, 32     # cohort, local steps, batch, CIFAR size


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _tree_spec(tree, sharding):
    return jax.tree.map(lambda a: _spec(a.shape, a.dtype, sharding), tree)


def _stage_round(stage):
    """(loss, abstract active/frozen/state/batches) of ResNet-18 at
    ``stage``, as ``RoundEngine`` builds them: stage 0 recomputing, later
    stages on the f32 feature cache."""
    model = CNN(RESNET18)
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    frozen, active = jax.eval_shape(
        lambda p: fz.init_cnn_stage_active(model, p, stage,
                                           jax.random.PRNGKey(1)), params)
    x = jax.ShapeDtypeStruct((B, IMG, IMG, 3), jnp.float32)
    if stage:
        loss = make_tiered_loss(fz.cnn_cached_stage_loss_fn(model, stage),
                                "f32")
        x = jax.eval_shape(lambda f, s, xx: fz.cnn_prefix_features(
            model, f, s, xx, stage), frozen, state, x)
        frozen = {}
    else:
        loss = fz.cnn_stage_loss_fn(model, 0)
    batches = {"x": jax.ShapeDtypeStruct((K, NB) + x.shape, x.dtype),
               "y": jax.ShapeDtypeStruct((K, NB, B), jnp.int32)}
    return loss, active, frozen, state, batches


def _compile(fn, *args):
    return (fn if hasattr(fn, "lower") else jax.jit(fn)).lower(*args).compile()


# ResNet-18 stage-0 active leaves (stem, stage0 convs, output-module convs)
# at compress_ratio 0.1, up to the VMEM dispatch bound
@pytest.mark.parametrize("length", [1728, 36864, 294912,
                                    sparse_agg.MAX_VMEM_ELEMS])
def test_sparse_agg_compiles(one_chip, length):
    k = topk_keep(length, 0.1)
    compiled = _compile(
        lambda i, v, w: sparse_agg.sparse_cohort_add_fwd(i, v, w, length),
        _spec((K, k), jnp.int32, one_chip),
        _spec((K, k), jnp.float32, one_chip),
        _spec((K,), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("stage", [0, 3])
def test_resnet18_fused_round_compiles(one_chip, stage):
    loss, active, frozen, state, batches = _stage_round(stage)
    fn = make_fused_round(loss, sgd(0.05), unroll=False)
    compiled = _compile(
        fn, _tree_spec(active, one_chip), _tree_spec(frozen, one_chip),
        _tree_spec(state, one_chip), _tree_spec(batches, one_chip),
        _spec((K,), jnp.int32, one_chip), _spec((K,), jnp.float32, one_chip))
    mem = compiled.memory_analysis()
    assert mem.temp_size_in_bytes + mem.argument_size_in_bytes < 16e9


def test_resnet18_compressed_pallas_round_compiles(one_chip, monkeypatch):
    """The one-chip compressed round with the Pallas fold in the graph
    (compiled, as on the chip, not interpreted as the CPU backend would)."""
    monkeypatch.setattr(ops, "_default_interpret", lambda: False)
    loss, active, frozen, state, batches = _stage_round(3)
    fn = make_fused_round(loss, sgd(0.05), unroll=False, compress_ratio=0.1,
                          use_pallas=True)
    res = jax.tree.map(lambda a: _spec((K, a.size), jnp.float32, one_chip),
                       active)
    compiled = _compile(
        fn, _tree_spec(active, one_chip), _tree_spec(frozen, one_chip),
        _tree_spec(state, one_chip), _tree_spec(batches, one_chip),
        _spec((K,), jnp.int32, one_chip), _spec((K,), jnp.float32, one_chip),
        res)
    assert "tpu_custom_call" in compiled.as_text()


def test_resnet18_sharded_round_compiles(topo):
    mesh = make_client_mesh(4, devices=topo.devices)
    rep, cli = NamedSharding(mesh, P()), NamedSharding(mesh, P("clients"))
    loss, active, frozen, state, batches = _stage_round(0)
    fn = make_fused_round(loss, sgd(0.05), mesh=mesh)
    compiled = _compile(
        fn, _tree_spec(active, rep), _tree_spec(frozen, rep),
        _tree_spec(state, rep), _tree_spec(batches, cli),
        _spec((K,), jnp.int32, cli), _spec((K,), jnp.float32, cli))
    hlo = compiled.as_text()
    assert "all-reduce" in hlo


def test_dequant_matmul_compiles(one_chip):
    """int8 cache rows x a 512-wide weight, the default 256 blocks."""
    M, D, N = 8192, 512, 512
    compiled = _compile(
        lambda q, s, w: dequant_matmul.dequant_matmul_fwd(q, s, w),
        _spec((M, D), jnp.int8, one_chip),
        _spec((M, 1), jnp.float32, one_chip),
        _spec((D, N), jnp.float32, one_chip))
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.xfail(strict=True, raises=ValueError, reason=(
    "Mosaic refuses the (1, block_q, 1, d) blocks: 'The Pallas TPU lowering "
    "currently requires that the last two dimensions of your block shape "
    "are divisible by 8 and 128 respectively, or be equal to the respective "
    "dimensions of the overall array.' (ROADMAP A2)"))
def test_flash_attention_compiles(one_chip):
    """GQA at Llama-3-8B head widths: 32 query / 8 KV heads of 128."""
    Bq, S, Hq, Hkv, D = 1, 2048, 32, 8, 128
    compiled = _compile(
        lambda q, k, v: flash_attention.flash_attention_fwd(q, k, v),
        _spec((Bq, S, Hq, D), jnp.bfloat16, one_chip),
        _spec((Bq, S, Hkv, D), jnp.bfloat16, one_chip),
        _spec((Bq, S, Hkv, D), jnp.bfloat16, one_chip))
    assert "tpu_custom_call" in compiled.as_text()
