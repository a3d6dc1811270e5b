"""The yardstick pinned. For each cell, and for the pair of configuration
and traffic that ``resnet18.s3.f32cache.k10`` names, kept out of
``BENCHMARK.json`` until its comparison catches its control: the pool, the
weights and the cohorts that a seed makes at the CPU tests' size, and the
counted FLOPs and samples of a round at the real size. The values were
recorded when the CNN's shapes still lived in the harness and in
``bench/flops.py``; moving a family's code into its own files may not
change them, since a change would move every metric read from them."""
import hashlib
import json

import jax
import numpy as np
import pytest

from conftest import ROOT
from bench import flops, harness

SEED = 2147483911
COHORTS = "66df53efab79e3ee52c0a1808398f7f89bc109cdef31191fa3a5b5ed9d0df3a0"
# (configuration, traffic) of each pinned cell
CELLS = {"resnet18.s0.k10": ("resnet18", "s0.k10"),
         "vgg16_bn.s3.recompute.k10": ("vgg16_bn", "s3.recompute.k10"),
         "resnet18.s3.f32cache.k10": ("resnet18", "s3.f32cache.k10")}
PINS = {
    "resnet18.s0.k10": {
        "flops_per_round": 6432541900800.0, "samples_per_round": 5120,
        "pool": "fc0f80f28f112a5a9c0d3bac03ee3ed78d136d1eadc7454c43a7db69e0cfa694",
        "weights": "aabeaa85ce5062cd6e96aa3ea4671293e4d87037690606a2660ee76cd808cbde",
        "cohorts": COHORTS},
    "vgg16_bn.s3.recompute.k10": {
        "flops_per_round": 4922592460800.0, "samples_per_round": 5120,
        "pool": "d93c21084d9661cd70a57500306a6b311a5cdbdd73456919007e7db5ce9b4c40",
        "weights": "3f34d784ed7cc7c21952504f40983d0eca189faee1d30a8063415e7f02a5ff01",
        "cohorts": COHORTS},
    "resnet18.s3.f32cache.k10": {
        "flops_per_round": 4123325890560.0, "samples_per_round": 5120,
        "pool": "fc0f80f28f112a5a9c0d3bac03ee3ed78d136d1eadc7454c43a7db69e0cfa694",
        "weights": "7411285370e086c1f95c3904790091bcf751af12c656ddb6379824f61ee7980b",
        "cohorts": COHORTS},
}


def _digest(*arrays) -> str:
    h = hashlib.sha256()
    for a in arrays:
        a = np.ascontiguousarray(a)
        h.update(f"{a.dtype}{a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def _files(root, cell):
    config, traffic = CELLS[cell]
    return {"config": json.loads((root / "bench" / "configs"
                                  / f"{config}.json").read_text()),
            "traffic": json.loads((root / "bench" / "traffic"
                                   / f"{traffic}.json").read_text())}


def _world_digests(files, seed):
    w = harness.World(files, seed)
    cohorts = w.lead_cohorts + [next(w.cohorts) for _ in range(5)]
    return {"pool": _digest(w.pool_x, w.labels, np.asarray(w.seeds)),
            "weights": _digest(*jax.tree.leaves((w.frozen_host, w.start))),
            "cohorts": _digest(np.asarray(cohorts))}


@pytest.mark.parametrize("cell", sorted(PINS))
def test_yardstick_unchanged(checkout, cell):
    real = _files(ROOT, cell)
    got = {"flops_per_round": flops.flops_per_round(real["config"],
                                                    real["traffic"]),
           "samples_per_round": flops.samples_per_round(real["traffic"])}
    got.update(_world_digests(_files(checkout, cell), SEED))
    assert got == PINS[cell]
