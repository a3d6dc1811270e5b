"""``bench/flops.py`` by hand, and against XLA's own count."""
import json

import jax
import jax.numpy as jnp
import pytest

from conftest import ROOT
from bench import flops

RESNET18 = json.loads((ROOT / "bench/configs/resnet18.json").read_text())
VGG16 = json.loads((ROOT / "bench/configs/vgg16_bn.json").read_text())
M = 1e6


def test_resnet18_forward_total_and_stages():
    """He et al.'s CIFAR ResNet-18 at 32x32: 0.56 GMAC forward. Stem 3x3x3
    to 64 at 32x32; stage 0 four 64->64 convs at 32x32; stages 1-3 a
    stride-2 conv, three full convs and a 1x1 projection each."""
    assert flops.model_forward_macs(RESNET18) == pytest.approx(0.556e9,
                                                               rel=2e-3)
    last = flops.layer_macs(RESNET18, 3)
    stem = 32 * 32 * 9 * 3 * 64
    stage0 = 4 * 32 * 32 * 9 * 64 * 64
    # stage 1 at 16x16: stride-2 64->128, 128->128, 1x1 projection, and
    # a second block of two 128->128; stages 2 and 3 cost the same
    stage1 = 16 * 16 * 9 * (64 * 128 + 3 * 128 * 128) + 16 * 16 * 64 * 128
    assert stage1 == pytest.approx(134.2 * M, rel=2e-3)
    assert sum(last["prefix"]) == stem + stage0 + 2 * stage1
    assert sum(last["active"]) == stage1
    assert last["head"] == [512 * 10]


def test_counting_rule_per_tier():
    """Trained layers x 3; the prefix once, and only where recomputed."""
    s0 = flops.flops_per_sample(RESNET18, 0, recompute_prefix=False)
    assert s0 == pytest.approx(1.256e9, rel=2e-3)
    # stage 0 has no prefix: recomputing changes nothing
    assert flops.flops_per_sample(RESNET18, 0, recompute_prefix=True) == s0
    cached = flops.flops_per_sample(RESNET18, 3, recompute_prefix=False)
    assert cached == pytest.approx(0.805e9, rel=2e-3)
    vgg_cached = flops.flops_per_sample(VGG16, 3, recompute_prefix=False)
    vgg_recompute = flops.flops_per_sample(VGG16, 3, recompute_prefix=True)
    assert vgg_cached == pytest.approx(0.581e9, rel=2e-3)
    assert vgg_recompute - vgg_cached == pytest.approx(0.381e9, rel=2e-3)
    traffic = json.loads((ROOT / "bench/traffic/s3.recompute.k10.json")
                         .read_text())
    assert flops.samples_per_round(traffic) == 10 * 16 * 32
    assert flops.flops_per_round(VGG16, traffic) == pytest.approx(
        vgg_recompute * 5120)
    traffic["cache_tier"] = "f32"
    assert flops.flops_per_round(VGG16, traffic) == pytest.approx(
        vgg_cached * 5120)


@pytest.mark.parametrize("cfg", [RESNET18, VGG16], ids=["resnet18",
                                                        "vgg16_bn"])
def test_matches_xla_cost_analysis(cfg):
    """A one-sample forward of the program's model at published widths,
    compiled for the CPU: XLA counts 2 FLOPs per multiply-add on the image
    (not on the zero padding) and a few percent more for batch norm,
    activations and pooling."""
    from bench import harness
    from repro.models.cnn import CNN

    driver = harness.load_module(harness.BENCH / "drivers" / "cnn.py",
                                 "bench_driver_cnn")
    model = CNN(driver.model_config(cfg))
    params, state = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    x = jax.ShapeDtypeStruct((1, 32, 32, 3), jnp.float32)
    fwd = jax.jit(lambda p, s, x: model.apply(p, s, x, train=False)[0])
    cost = fwd.lower(params, state, x).compile().cost_analysis()
    if isinstance(cost, list):
        cost = cost[0]
    counted = 2 * flops.model_forward_macs(cfg, full_kernel=False)
    assert counted <= cost["flops"] <= 1.05 * counted
