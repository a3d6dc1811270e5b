"""Host staging of the fused round (``RoundEngine._gather``): each cohort
is written with one copy into buffers the engine reuses from round to
round. The staged arrays must match the two-copy gather they replace
(fancy indexing per client, then ``np.stack``, then pad rows) byte for
byte, and reusing the buffers must leave every round's results as a
fresh engine's."""
import jax
import numpy as np
import pytest

from repro.core import freezing_cnn as fz
from repro.data.partition import dirichlet_partition
from repro.data.synthetic import SyntheticVision
from repro.fl.client import SimClient, batch_index_plan, make_client_fleet
from repro.fl.engine import RoundEngine
from repro.models.cnn import CNN, CNNConfig
from repro.optim import sgd

TINY = CNNConfig("tiny_resnet", "resnet", stage_sizes=(1, 1),
                 stage_channels=(8, 16), num_classes=4)
ALLOC = "engine.stage_alloc_bytes"


def _clients(sizes):
    rng = np.random.RandomState(0)
    return {cid: SimClient(cid, {"x": rng.randn(n, 6, 6, 3).astype(np.float32),
                                 "y": rng.randint(0, 4, n).astype(np.int32)},
                           memory_bytes=2**30, capability=1.0, seed=cid)
            for cid, n in enumerate(sizes)}


def _gather_engine(batch_size, local_epochs):
    """An engine for ``_gather`` alone; its cached tiers encode 3x."""
    return RoundEngine(loss_fn=None, optimizer=None, cached_loss_fn=object(),
                       feature_fn=lambda x: x * 3.0, batch_size=batch_size,
                       local_epochs=local_epochs)


def two_copy_gather(eng, clients, cids, round_idx, tier, pad):
    """The gather the staging buffers replace: ``data[idx]`` per client,
    ``np.stack`` over the cohort, pad rows repeating row 0."""
    bs, ep = eng.batch_size, eng.local_epochs
    plans = {cid: batch_index_plan(clients[cid].num_samples, bs, ep,
                                   clients[cid].round_seed(round_idx))
             for cid in cids}
    nb = max(max(len(p) for p in plans.values()), 1)
    out = {}
    for key in eng._client_arrays(clients[cids[0]], tier):
        rows = []
        for cid in cids:
            data = eng._client_arrays(clients[cid], tier)[key]
            plan = plans[cid]
            idx = np.stack([plan[t % len(plan)] if plan
                            else np.zeros(bs, np.int64) for t in range(nb)])
            rows.append(data[idx])
        v = np.stack(rows)
        if pad:
            v = np.concatenate([v, np.repeat(v[:1], pad, axis=0)])
        out[key] = v
    nb_live = np.asarray([len(plans[cid]) for cid in cids], np.int32)
    return out, nb_live


@pytest.mark.parametrize("sizes,epochs,tier,pad", [
    # 5 batches, 2, none (the zero plan), 4; two epochs: nb 10, the
    # shorter plans cycle
    ((40, 17, 5, 33), 2, None, 0),
    ((24, 16, 40), 1, "int8", 0),
    ((24, 16, 40), 1, None, 3),
    ((40, 17, 5), 2, "int8", 1),
], ids=["uneven_cycled_2epochs", "int8_tier", "pad_rows", "int8_padded"])
def test_staged_cohort_matches_two_copy_gather(sizes, epochs, tier, pad):
    clients = _clients(sizes)
    cids = sorted(clients)[::-1]
    eng = _gather_engine(8, epochs)
    staged = []
    for r in range(2):
        eng.spans.reset()
        stacked, nb_live, weights = eng._gather(clients, cids, r, tier, pad)
        want, want_live = two_copy_gather(eng, clients, cids, r, tier, pad)
        assert list(stacked) == list(want)
        for k in want:
            assert stacked[k].dtype == want[k].dtype, k
            assert stacked[k].shape == want[k].shape, k
            assert stacked[k].tobytes() == want[k].tobytes(), (k, r)
        np.testing.assert_array_equal(nb_live, want_live)
        np.testing.assert_array_equal(
            weights, np.asarray([clients[c].num_samples for c in cids],
                                np.float32))
        counters = eng.spans.snapshot()["counters"]
        if r == 0:
            assert counters == {ALLOC: sum(v.nbytes for v in want.values())}
        else:
            assert counters == {}
            assert all(stacked[k] is staged[0][k] for k in stacked)
        staged.append(stacked)
    if tier == "int8":
        assert set(staged[0]) == {"x", "y", "x_scale"}
        assert staged[0]["x"].dtype == np.int8


def test_a_new_shape_allocates_anew():
    """A cohort of another size or step count gets a fresh buffer, counted
    once; the old shape's buffer is not written."""
    clients = _clients((24, 16, 40, 8))
    eng = _gather_engine(8, 1)
    first, _, _ = eng._gather(clients, [0, 1], 0, None)
    kept = {k: v.copy() for k, v in first.items()}
    eng.spans.reset()
    second, _, _ = eng._gather(clients, [0, 1, 2], 0, None)
    want, _ = two_copy_gather(eng, clients, [0, 1, 2], 0, None, 0)
    assert eng.spans.snapshot()["counters"] == {
        ALLOC: sum(v.nbytes for v in want.values())}
    assert all(second[k].tobytes() == want[k].tobytes() for k in want)
    assert all(first[k].tobytes() == kept[k].tobytes() for k in kept)


def test_mixed_dtypes_widen_as_np_stack_did():
    clients = _clients((24, 16, 40))
    clients[1].data["y"] = clients[1].data["y"].astype(np.int64)
    eng = _gather_engine(8, 1)
    stacked, _, _ = eng._gather(clients, [0, 1, 2], 0, None)
    want, _ = two_copy_gather(eng, clients, [0, 1, 2], 0, None, 0)
    assert stacked["y"].dtype == want["y"].dtype == np.int64
    assert stacked["y"].tobytes() == want["y"].tobytes()


def test_an_array_shorter_than_its_client_raises():
    """"clip" would repeat the last row where the plan runs past an array;
    the gather refuses such a client, as fancy indexing did."""
    clients = _clients((24, 16))
    clients[1].data["x"] = clients[1].data["x"][:9]
    eng = _gather_engine(8, 1)
    with pytest.raises(IndexError, match="client 1's 'x' has 9 rows"):
        eng._gather(clients, [0, 1], 0, None)


@pytest.fixture(scope="module")
def world():
    sv = SyntheticVision(num_classes=4, image_size=16, seed=0)
    train = sv.sample(256, seed=1)
    parts = dirichlet_partition(train["y"], 4, alpha=1.0, seed=0)
    clients = make_client_fleet(train, parts, scenario="low", seed=0)
    model = CNN(TINY)
    params, state = model.init(jax.random.PRNGKey(0))
    frozen, active = fz.init_cnn_stage_active(model, params, 0,
                                              jax.random.PRNGKey(1))
    return {c.client_id: c for c in clients}, model, frozen, active, state


def _round_engine(model, frozen):
    return RoundEngine(loss_fn=fz.cnn_stage_loss_fn(model, 0),
                       optimizer=sgd(0.05), frozen=frozen, batch_size=8,
                       local_epochs=1)


def _host(tree):
    return [np.asarray(x).copy() for x in jax.tree.leaves(tree)]


def _same(a, b):
    return all(x.dtype == y.dtype and x.tobytes() == y.tobytes()
               for x, y in zip(_host(a), _host(b)))


def test_reused_buffers_leak_nothing_between_rounds(world):
    """Three fused rounds on one engine, whose buffers are rewritten each
    round, equal bit for bit the same rounds on a fresh engine each round
    (empty staging, the same compiled program); and a round's returned
    params, state and losses stay as they were when the next round
    rewrites the buffers (the CPU backend may alias host arrays)."""
    by_id, model, frozen, active, state = world
    cids = sorted(by_id)[:3]
    eng = _round_engine(model, frozen)
    p, s = active, state
    rp, rs = active, state
    prev = None
    for r in range(3):
        eng.spans.reset()
        p, s, losses = eng.run_round(by_id, cids, p, s, r)
        assert (ALLOC in eng.spans.snapshot()["counters"]) == (r == 0)
        if prev is not None:
            old_p, old_s, old_losses, snap = prev
            assert _same(old_p, snap[0]) and _same(old_s, snap[1])
            assert old_losses == snap[2]
        fresh = _round_engine(model, frozen)
        fresh._jit_cache = eng._jit_cache
        rp, rs, r_losses = fresh.run_round(by_id, cids, rp, rs, r)
        assert ALLOC in fresh.spans.snapshot()["counters"]
        assert _same(p, rp) and _same(s, rs), r
        assert losses == r_losses, r
        prev = (p, s, losses, (_host(p), _host(s), dict(losses)))


def test_a_round_that_raises_drops_its_buffers(world):
    """A round that raises between the put and the sync leaves no buffer
    the device may still read: the next round allocates afresh and runs
    as a fresh engine would."""
    by_id, model, frozen, active, state = world
    cids = sorted(by_id)[:3]
    eng = _round_engine(model, frozen)
    eng.run_round(by_id, cids, active, state, 0)
    old = dict(eng._stage)
    assert set(old) == {(None, "x"), (None, "y")}
    program = eng._jit_cache["fused"]

    def lost(*args):
        raise RuntimeError("device lost")

    eng._jit_cache["fused"] = lost
    with pytest.raises(RuntimeError, match="device lost"):
        eng.run_round(by_id, cids, active, state, 1)
    assert eng._stage == {}
    eng._jit_cache["fused"] = program
    eng.spans.reset()
    p, s, losses = eng.run_round(by_id, cids, active, state, 1)
    assert eng.spans.snapshot()["counters"][ALLOC] == sum(
        v.nbytes for v in old.values())
    assert all(eng._stage[k] is not old[k] for k in old)
    fresh = _round_engine(model, frozen)
    fresh._jit_cache = eng._jit_cache
    rp, rs, r_losses = fresh.run_round(by_id, cids, active, state, 1)
    assert _same(p, rp) and _same(s, rs) and losses == r_losses
