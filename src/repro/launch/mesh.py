"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state. Single pod: (data=16, model=16) = 256 chips.
Multi-pod: (pod=2, data=16, model=16) = 512 chips — the "pod" axis is the
federated cross-silo axis (DESIGN.md §2).
"""
from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

from repro.dist.sharding import CLIENT_AXIS


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_debug_mesh(n_devices: int = 1):
    """Tiny mesh over whatever devices exist (tests/smoke)."""
    n = min(n_devices, len(jax.devices()))
    return jax.make_mesh((1, n), ("data", "model"))


def make_client_mesh(n_devices: int | None = None, *, devices=None):
    """1-D mesh over the federated cohort axis (``"clients"``).

    The fused round engine (``fl/engine.py``) shard_maps the per-client
    local training over this axis: clients partition across devices, params
    replicate, and the Eq. 1 aggregation is one cross-device ``psum``.
    Defaults to every visible device (or every one of ``devices``, e.g. a
    described topology's); asking for more than are visible raises. CPU
    testing forces extra host devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set BEFORE jax
    import — see tests/test_shard.py and ``benchmarks/run.py shard_scale``).

    The axis is ``Auto``: arrays placed on the mesh carry no sharding in
    their types, so jitted code outside the shard_mapped round (feature
    extraction, host folds) mixes them freely with single-device arrays."""
    devices = list(jax.devices() if devices is None else devices)
    n = len(devices) if n_devices is None else n_devices
    if not 1 <= n <= len(devices):
        raise ValueError(f"client mesh of {n} devices requested, but "
                         f"{len(devices)} are visible (on CPU, set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N before "
                         "jax initializes)")
    return Mesh(np.asarray(devices[:n]), (CLIENT_AXIS,),
                axis_types=(AxisType.Auto,))


# TPU v5e hardware constants (per chip) — §Roofline denominators
PEAK_FLOPS_BF16 = 197e12      # FLOP/s
HBM_BW = 819e9                # B/s
ICI_BW = 50e9                 # B/s per link
