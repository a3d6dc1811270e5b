"""Host spans and counters (``repro.spans``), the engine's spans along a
fused round, and the device scopes the round program carries in its op
metadata."""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import spans as spans_mod
from repro.core import freezing_cnn as fz
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import SyntheticVision
from repro.fl.client import make_client_fleet
from repro.fl.engine import RoundEngine, make_fused_round
from repro.models.cnn import CNN, CNNConfig
from repro.optim import sgd
from repro.spans import SpanStats

TINY = CNNConfig("tiny_resnet", "resnet", stage_sizes=(1, 1),
                 stage_channels=(8, 16), num_classes=4)
ROUND_SPANS = {"engine.round", "engine.gather", "engine.put",
               "engine.dispatch", "engine.sync"}


@pytest.fixture
def clock(monkeypatch):
    """A fake ``perf_counter_ns`` that reads the times pushed on it."""
    ticks = []
    monkeypatch.setattr(spans_mod, "time", types.SimpleNamespace(
        perf_counter_ns=lambda: ticks.pop(0)))
    return ticks


def test_nesting_and_self_time(clock):
    """outer [0, 100] holds a [10, 30] and b [40, 90]; b holds c [50, 60].
    Self time is the duration less the children's."""
    s = SpanStats()
    for base in (0, 1000):
        clock.extend(base + t for t in (0, 10, 30, 40, 50, 60, 90, 100))
        with s.span("outer"):
            with s.span("a"):
                pass
            with s.span("b"):
                with s.span("c"):
                    pass
    snap = s.snapshot()["spans"]
    assert snap == {
        "outer": {"count": 2, "total_ns": 200, "self_ns": 60},
        "a": {"count": 2, "total_ns": 40, "self_ns": 40},
        "b": {"count": 2, "total_ns": 100, "self_ns": 80},
        "c": {"count": 2, "total_ns": 20, "self_ns": 20}}


def test_a_span_closes_on_an_exception(clock):
    s = SpanStats()
    clock.extend([0, 5, 7, 10])
    with s.span("outer"):
        with pytest.raises(ValueError):
            with s.span("inner"):
                raise ValueError
    snap = s.snapshot()["spans"]
    assert snap["inner"] == {"count": 1, "total_ns": 2, "self_ns": 2}
    assert snap["outer"] == {"count": 1, "total_ns": 10, "self_ns": 8}


def test_counters_snapshot_and_reset():
    s = SpanStats()
    s.add("bytes", 3)
    s.add("bytes", np.int64(4))
    with s.span("x"):
        pass
    snap = s.snapshot()
    assert snap["counters"] == {"bytes": 7}
    assert snap["spans"]["x"]["count"] == 1
    s.add("bytes", 1)
    assert snap["counters"] == {"bytes": 7}      # a copy, not a view
    s.reset()
    assert s.snapshot() == {"spans": {}, "counters": {}}


def test_no_span_name_says_compile():
    """The benchmark's trace reduction takes host events that match
    ``compile`` for compiles."""
    import inspect
    import re

    from repro.fl import engine

    names = set(re.findall(r'"(engine\.[\w.]+)"', inspect.getsource(engine)))
    assert ROUND_SPANS < names
    assert not any("compile" in n for n in names)


def _world(n_clients=4, n=256):
    sv = SyntheticVision(num_classes=4, image_size=16, seed=0)
    train = sv.sample(n, seed=1)
    parts = dirichlet_partition(train["y"], n_clients, alpha=1.0, seed=0)
    clients = make_client_fleet(train, parts, scenario="low", seed=0)
    model = CNN(TINY)
    params, state = model.init(jax.random.PRNGKey(0))
    return {c.client_id: c for c in clients}, model, params, state


def _engine(model, stage, frozen, state, batch_size):
    cached_loss = feature_fn = None
    if stage > 0:
        cached_loss = fz.cnn_cached_stage_loss_fn(model, stage)
        feature_fn = lambda x: fz.cnn_prefix_features(model, frozen, state,
                                                      x, stage)
    return RoundEngine(loss_fn=fz.cnn_stage_loss_fn(model, stage),
                       optimizer=sgd(0.05), frozen=frozen,
                       cached_loss_fn=cached_loss, feature_fn=feature_fn,
                       batch_size=batch_size, local_epochs=1)


def test_fused_round_records_each_span_once():
    """One undivided cohort: each engine span closes once a round, the
    children within ``engine.round``, and the bytes counted are those of
    the stacked batches, live step counts and weights. The staging
    buffers of the stacked batches are allocated in round 0 alone."""
    by_id, model, params, state = _world()
    frozen, active = fz.init_cnn_stage_active(model, params, 0,
                                              jax.random.PRNGKey(1))
    bs = 8
    eng = _engine(model, 0, frozen, state, bs)
    cids = sorted(by_id)[:3]
    for r in range(2):
        eng.spans.reset()
        active, state, _ = eng.run_round(by_id, cids, active, state, r)
        snap = eng.spans.snapshot()
        assert set(snap["spans"]) == ROUND_SPANS
        assert all(v["count"] == 1 for v in snap["spans"].values())
        total = snap["spans"]["engine.round"]["total_ns"]
        children = sum(v["total_ns"] for n, v in snap["spans"].items()
                       if n != "engine.round")
        assert children <= total
        assert sum(v["self_ns"] for v in snap["spans"].values()) == total
        nb = max(by_id[c].num_samples // bs for c in cids)
        x, y = by_id[cids[0]].data["x"], by_id[cids[0]].data["y"]
        rows = len(cids) * nb * bs
        staged = rows * x[0].nbytes + rows * y[0].nbytes
        want = staged + len(cids) * (4 + 4)     # int32 steps, f32 weights
        allocs = {"engine.stage_alloc_bytes": staged} if r == 0 else {}
        assert snap["counters"] == {"engine.h2d_bytes": want, **allocs}


def test_mixed_tiers_combine_and_extract_features():
    """Half the cohort on cached features: two groups, so the gather to
    sync spans close twice, the host fold once, and one extraction per
    cached client."""
    by_id, model, params, state = _world()
    stage = 1
    frozen, active = fz.init_cnn_stage_active(model, params, stage,
                                              jax.random.PRNGKey(1))
    eng = _engine(model, stage, frozen, state, 8)
    cids = sorted(by_id)
    eng.run_round(by_id, cids, active, state, 0,
                  use_cache={cids[0]: "f32", cids[2]: "f32"})
    counts = {n: v["count"] for n, v in eng.spans.snapshot()["spans"].items()}
    assert counts == {"engine.round": 1, "engine.gather": 2, "engine.put": 2,
                      "engine.dispatch": 2, "engine.sync": 2,
                      "engine.combine": 1, "engine.features": 2}


@pytest.mark.parametrize("unroll,compress_ratio",
                         [(True, None), (False, None), (False, 0.1)])
def test_round_program_carries_its_scopes(unroll, compress_ratio):
    """The lowered round names its local steps, its Eq. 1 fold and the
    frozen prefix's forward in the ops' locations, which become their
    op-name metadata."""
    _, model, params, state = _world()
    stage = 1
    frozen, active = fz.init_cnn_stage_active(model, params, stage,
                                              jax.random.PRNGKey(1))
    K, nb, bs = 2, 2, 4
    sds = jax.ShapeDtypeStruct
    args = [active, frozen, state,
            {"x": sds((K, nb, bs, 16, 16, 3), jnp.float32),
             "y": sds((K, nb, bs), jnp.int32)},
            sds((K,), jnp.int32), sds((K,), jnp.float32)]
    if compress_ratio is not None:
        args.append(jax.tree.map(lambda p: sds((K, p.size), jnp.float32),
                                 active))
    fn = make_fused_round(fz.cnn_stage_loss_fn(model, stage), sgd(0.05),
                          unroll=unroll, compress_ratio=compress_ratio)
    text = fn.lower(*args).as_text(debug_info=True)
    for scope in ("local_train", "fold", "prefix"):
        assert f"/{scope}" in text or f"({scope})" in text, scope
