"""Granite 4.0-H (Mamba-2 + NoPE GQA) against its plain float32 reference
(``hybrid_lm_reference.py``, the copy of ``bench/references/hybrid_lm.py``)
on seeded random weights at a toy width on the CPU: the chunked SSD scan,
the stage losses and gradients, one ``RoundEngine`` round, and the
published layer pattern.

Tolerances: both sides are float32 with every product at ``highest``; what
is left is the order of summation (chunked scan against the quadratic form
or the recurrence, blocked against dense attention, fused against plain
sums) over values of order one, a few ulps a step, which 1e-4 relative
covers with room. A product computed in bfloat16 (a relative error near
4e-3) exceeds it."""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import hybrid_lm_reference as ref
from repro import configs
from repro.configs.granite_4_0_h_micro import PUBLISHED, from_hf
from repro.core import freezing as fz
from repro.fl.client import SimClient
from repro.fl.engine import RoundEngine
from repro.models import ssm
from repro.models.transformer import LM
from repro.optim import sgd

ROOT = Path(__file__).resolve().parents[1]
RTOL = 1e-4
# the cut the benchmark runs (layers 0-9, 5 freeze blocks) at a toy width
CFG = ref.tiny(dict(PUBLISHED, num_hidden_layers=10, num_freeze_blocks=5))


def _program(cfg, stage):
    arch = from_hf(cfg, num_freeze_blocks=cfg["num_freeze_blocks"],
                   param_dtype="float32", compute_dtype="float32")
    model = LM(arch)
    return model, fz.make_stage_plan(arch, stage)


def _tokens(key, shape):
    return jax.random.randint(key, shape, 0, CFG["vocab_size"], jnp.int32)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30)


def test_reference_copy_is_the_benchmarks():
    assert ((ROOT / "tests" / "hybrid_lm_reference.py").read_text()
            == (ROOT / "bench" / "references" / "hybrid_lm.py").read_text())


def test_layer_kinds_are_the_published_pattern():
    # ibm-granite/granite-4.0-h-micro config.json, layer_types[:10]
    published = ["mamba", "mamba", "mamba", "mamba", "mamba", "attention",
                 "mamba", "mamba", "mamba", "mamba"]
    names = {"mamba2_mlp": "mamba", "attn_mlp": "attention"}
    full = configs.get("granite-4.0-h-micro")
    assert full.num_layers == 40 and full.vocab_size == 100352
    assert [names[k] for k in full.layer_kinds()[:10]] == published
    cut = from_hf(dict(PUBLISHED, num_hidden_layers=10))
    assert [names[k] for k in cut.layer_kinds()] == published
    assert cut.segments() == (("mamba2_mlp", 5), ("attn_mlp", 1),
                              ("mamba2_mlp", 4))
    assert ref.layer_kinds(dict(PUBLISHED, num_hidden_layers=10)) == published
    # zamba2's periodic shared attention is untouched
    z = configs.get("zamba2-7b")
    assert z.layer_kinds()[:6] == ("mamba2",) * 5 + ("shared_attn",)


def test_chunked_ssd_matches_recurrence_and_quadratic_form():
    """Four chunks of 8: the program's chunked scan, the step-by-step
    recurrence h_t = exp(dt_t a) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t,
    and the reference's quadratic form agree."""
    b, s, h, p, n, chunk = 2, 32, 3, 4, 5, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)) - 1.0)
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, n))
    cm = jax.random.normal(ks[4], (b, s, n))
    with jax.default_matmul_precision("highest"):
        chunked = ssm._ssd_chunked(x, bm, cm, dt, dt * a, chunk=chunk)
        quadratic = ref.ssd_quadratic(x, dt, a, bm, cm)

        def step(state, t):
            xt, dtt, bt, ct = t
            state = (jnp.exp(dtt * a)[..., None, None] * state
                     + jnp.einsum("bh,bhp,bn->bhpn", dtt, xt, bt))
            return state, jnp.einsum("bhpn,bn->bhp", state, ct)

        _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, n)),
                             (x.transpose(1, 0, 2, 3), dt.transpose(1, 0, 2),
                              bm.transpose(1, 0, 2), cm.transpose(1, 0, 2)))
    recurrent = ys.transpose(1, 0, 2, 3)
    assert _rel(chunked, recurrent) < RTOL
    assert _rel(quadratic, recurrent) < RTOL


@pytest.mark.parametrize("stage", [0, 2, 4])
def test_stage_loss_and_gradients_match_reference(stage):
    """Stage 0 trains the first block and the embedding through its
    lookup; stage 2 runs four Mamba-2 layers frozen and trains a Mamba-2
    and the attention layer under the output module; stage 4 trains the
    last block with the tied head."""
    frozen, active, state = jax.jit(
        lambda k: ref.init_weights(CFG, stage, k))(jax.random.PRNGKey(stage))
    model, plan = _program(CFG, stage)
    tokens = _tokens(jax.random.PRNGKey(10 + stage), (2, CFG["seq_len"]))
    loss_fn = fz.lm_round_loss_fn(model, plan)
    with jax.default_matmul_precision("highest"):
        (got, st), g_got = jax.value_and_grad(loss_fn, has_aux=True)(
            active, frozen, state, {"x": tokens})
        want, g_want = jax.value_and_grad(
            lambda a: ref.loss(CFG, stage, frozen, a, tokens))(active)
    assert st == {}
    assert abs(float(got) - float(want)) < RTOL * abs(float(want))
    assert jax.tree.structure(g_got) == jax.tree.structure(g_want)
    for path, leaf in jax.tree_util.tree_flatten_with_path(g_want)[0]:
        other = g_got
        for k in path:
            other = other[k.key]
        assert _rel(other, leaf) < RTOL, jax.tree_util.keystr(path)
    if stage == 0:   # the embedding trains through its lookup
        assert float(jnp.abs(g_got["embed"]).max()) > 0
    if stage == 4:   # the tied head: the embedding is in the trained tree
        assert "embed" in active and "final_norm" in active
        assert not fz.prefix_is_static(plan)


def test_round_engine_round_matches_reference():
    """One fused round of two clients (8 and 12 rows, batches of 4): the
    per-client mean losses and each leaf of the folded update."""
    stage, lr, clip = 2, 0.5, 1.0
    frozen, active, state = jax.jit(
        lambda k: ref.init_weights(CFG, stage, k))(jax.random.PRNGKey(7))
    model, plan = _program(CFG, stage)
    sizes = [8, 12]
    clients = {i: SimClient(
        client_id=i, data={"x": np.asarray(_tokens(jax.random.PRNGKey(20 + i),
                                                   (n, CFG["seq_len"]))),
                           "y": np.zeros(n, np.int32)},
        memory_bytes=0.0, capability=1.0, seed=30 + i)
        for i, n in enumerate(sizes)}
    engine = RoundEngine(loss_fn=fz.lm_round_loss_fn(model, plan),
                         optimizer=sgd(lr), frozen=frozen, batch_size=4,
                         local_epochs=1, clip_norm=clip)
    with jax.default_matmul_precision("highest"):
        p, s, losses = engine.run_round(clients, [0, 1], active, state, 0)
    reference = ref.Reference(CFG, stage, lr=lr, clip_norm=clip)
    plans = [ref.batch_plan(n, 4, 1, ref.round_seed(30 + i, 0))
             for i, n in enumerate(sizes)]
    host = jax.tree.map(np.asarray, (frozen, active))
    a_ref, s_ref, l_ref = reference.round(
        host[0], host[1], state, [c.data for c in clients.values()], plans,
        sizes)
    assert s == {} and s_ref == {}
    np.testing.assert_allclose([losses[0], losses[1]], l_ref, rtol=RTOL)
    start = dict(jax.tree_util.tree_flatten_with_path(active)[0])
    got = dict(jax.tree_util.tree_flatten_with_path(p)[0])
    moved = 0
    for path, want in jax.tree_util.tree_flatten_with_path(a_ref)[0]:
        d_ref = np.asarray(want) - np.asarray(start[path])
        d_got = np.asarray(got[path]) - np.asarray(start[path])
        moved += np.linalg.norm(d_ref) > 0
        # an update is a difference of params of order one: its relative
        # error is the params' round-off over the update's size, 10x RTOL
        assert _rel(d_got, d_ref) < 10 * RTOL, jax.tree_util.keystr(path)
    assert moved == len(got)
