"""Differential conformance harness for the Pallas hot-path kernels.

Every Pallas kernel in ``kernels/`` ships with a pure-``lax`` reference in
``kernels/ref.py``; these tests are the contract between them. The two
roofline-ordered hot paths added for the tiered/compressed rounds get the
deepest coverage:

  * ``dequant_matmul`` — fused int8-dequant -> GEMM with per-(sample,
    channel) scales applied in-register (kernels/dequant_matmul.py).
  * ``sparse_cohort_add`` — one-kernel Eq. 1 fold of K clients' top-k
    (idx, vals) uplink rows (kernels/sparse_agg.py).

Structure: hypothesis-driven shape/dtype sweeps (ragged tails, non-divisible
block tilings), adversarial values (denormals, all-zero quantization groups,
near-overflow magnitudes), ``custom_vjp`` gradient checks against
``jax.grad`` of the reference, and end-to-end ``use_pallas=True`` federated
rounds allclose to the XLA default — up to a 2-stage SmartFreeze trajectory.

Tolerance convention: the Pallas GEMM accumulates split-K tiles in grid
order while the XLA dot uses a single fused reduction, so f32 results can
disagree by accumulation-order noise that is *relative to the magnitude of
the summands*, not the (possibly cancelled-to-small) output. ``_close``
therefore scales atol by ``max(1, |want|_inf)``. Gradient probes are LINEAR
(``sum(probe * out)``) for the same reason — a nonlinear probe like ``sin``
at large outputs amplifies forward noise into the cotangents.

All tests run the kernels in interpret mode on CPU (``ops`` defaults
``interpret=True`` off-TPU), so CI executes the real kernel bodies.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings
from hypothesis import strategies as st

from repro.fl import quant
from repro.fl.compression import (ingraph_compress_leaf,
                                  ingraph_sparse_aggregate)
from repro.fl.engine import make_fused_round
from repro.kernels import ops, ref, sparse_agg
from repro.kernels.dequant_matmul import normalize_scale
from repro.optim import sgd

pytestmark = pytest.mark.kernels

jax.config.update("jax_platform_name", "cpu")


def _close(got, want, tol=1e-5):
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    assert got.shape == want.shape
    assert np.all(np.isfinite(got) == np.isfinite(want))
    atol = tol * max(1.0, float(np.max(np.abs(want))) if want.size else 1.0)
    np.testing.assert_allclose(got, want, rtol=tol, atol=atol)


def _rand(seed, shape, scale=1.0):
    return jnp.asarray(
        np.random.RandomState(seed).randn(*shape) * scale, jnp.float32)


# ---------------------------------------------------------------------------
# dequant_matmul: forward conformance
# ---------------------------------------------------------------------------


def test_dqmm_int8_row_scales_matches_ref():
    """The production configuration: int8 cache rows + [N, 1] quantizer
    scales, exactly as ``quant.quantize_int8`` emits for 2-D features."""
    x = _rand(0, (32, 48), 3.0)
    q, scale = quant.quantize_int8(x)
    w = _rand(1, (48, 16))
    got = ops.dequant_matmul(q, scale, w)
    want = ref.dequant_matmul_ref(q, scale, w)
    _close(got, want)


@settings(max_examples=12, deadline=None)
@given(m=st.integers(1, 70), k=st.integers(1, 70), n=st.integers(1, 70),
       block=st.sampled_from([8, 16, 32]))
def test_dqmm_shape_sweep(m, k, n, block):
    """Hypothesis sweep over ragged (M, K, N) x block tilings: tails that
    do not divide the block shape are zero-padded by the wrapper and must
    never leak into the valid region."""
    x = _rand(m * 1000 + k * 10 + n, (m, k), 2.0)
    q, scale = quant.quantize_int8(x)
    w = _rand(7, (k, n))
    got = ops.dequant_matmul(q, scale, w,
                             block_m=block, block_n=block, block_k=block)
    _close(got, ref.dequant_matmul_ref(q, scale, w))


@pytest.mark.parametrize("kind", ["row", "col", "full", "scalar"])
def test_dqmm_scale_kinds(kind):
    """All four broadcast layouts the wrapper normalizes: per-row [M, 1],
    per-column [1, K] / [K], dense [M, K], and a 0-d scalar."""
    M, K, N = 19, 33, 11
    q = _rand(3, (M, K), 4.0).astype(jnp.int8)
    shapes = {"row": (M, 1), "col": (K,), "full": (M, K), "scalar": ()}
    scale = jnp.abs(_rand(4, shapes[kind])) + 0.01
    w = _rand(5, (K, N))
    got = ops.dequant_matmul(q, scale, w, block_m=16, block_n=16, block_k=16)
    _close(got, ref.dequant_matmul_ref(q, scale, w))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)])
def test_dqmm_float_inputs(dtype, tol):
    """Float (non-quantized) q values: the kernel upcasts to f32 before the
    scale multiply, so bf16 inputs lose only their own storage precision."""
    q = _rand(11, (24, 40)).astype(dtype)
    scale = jnp.abs(_rand(12, (24, 1))) + 0.1
    w = _rand(13, (40, 8))
    got = ops.dequant_matmul(q, scale, w, block_m=16, block_n=16, block_k=16)
    _close(got, ref.dequant_matmul_ref(q.astype(jnp.float32), scale, w), tol)


@pytest.mark.parametrize("shape", [(1, 1, 1), (5, 3, 2), (257, 129, 65)])
def test_dqmm_ragged_tails(shape):
    """Degenerate and prime-adjacent shapes against the default 256-blocks:
    every dimension exercises the pad-and-slice path."""
    M, K, N = shape
    x = _rand(M + K + N, (M, K), 2.0)
    q, scale = quant.quantize_int8(x)
    w = _rand(99, (K, N))
    _close(ops.dequant_matmul(q, scale, w),
           ref.dequant_matmul_ref(q, scale, w))


def test_dqmm_zero_amax_channels():
    """All-zero quantization groups: ``quantize_int8`` assigns scale 1.0
    (q is 0 there), so the corresponding output rows must be exactly 0."""
    x = _rand(21, (16, 24), 2.0)
    x = x.at[3].set(0.0).at[11].set(0.0)
    q, scale = quant.quantize_int8(x)
    w = _rand(22, (24, 6))
    got = ops.dequant_matmul(q, scale, w, block_m=8, block_n=8, block_k=8)
    _close(got, ref.dequant_matmul_ref(q, scale, w))
    assert np.all(np.asarray(got)[[3, 11]] == 0.0)


def test_dqmm_denormal_scales():
    """Sub-normal f32 scales (~1e-40): the in-register multiply must follow
    the reference through gradual underflow, not flush differently."""
    q = _rand(31, (12, 20), 40.0).astype(jnp.int8)
    scale = jnp.full((12, 1), 1e-40, jnp.float32)
    w = _rand(32, (20, 4))
    got = ops.dequant_matmul(q, scale, w, block_m=8, block_n=8, block_k=8)
    _close(got, ref.dequant_matmul_ref(q, scale, w))


def test_dqmm_near_overflow_magnitudes():
    """+-1e19-scale values: products reach ~1e38 (just inside f32 max).
    The f32 accumulator must match the reference without spurious inf."""
    q = jnp.asarray([[1, -2], [3, 4]], jnp.int8)
    scale = jnp.asarray([[1e19], [1e18]], jnp.float32)
    w = jnp.asarray([[1.0, -0.5], [0.25, 1.0]], jnp.float32)
    got = ops.dequant_matmul(q, scale, w, block_m=8, block_n=8, block_k=8)
    want = ref.dequant_matmul_ref(q, scale, w)
    assert np.all(np.isfinite(np.asarray(got)))
    _close(got, want)


def test_dqmm_out_dtype():
    q, scale = quant.quantize_int8(_rand(41, (16, 16)))
    w = _rand(42, (16, 16))
    got = ops.dequant_matmul(q, scale, w, out_dtype=jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    _close(got.astype(jnp.float32),
           ref.dequant_matmul_ref(q, scale, w, out_dtype=jnp.bfloat16
                                  ).astype(jnp.float32), 1e-2)


def test_dqmm_bad_scale_shape_raises():
    q = jnp.zeros((4, 8), jnp.int8)
    with pytest.raises(ValueError):
        normalize_scale(jnp.ones((4, 8, 1)), 4, 8)
    with pytest.raises(ValueError):
        normalize_scale(jnp.ones((3, 5)), 4, 8)  # matches neither M nor K


# ---------------------------------------------------------------------------
# dequant_matmul: custom_vjp gradients vs jax.grad of the reference
# ---------------------------------------------------------------------------


def test_dqmm_grad_matches_ref_linear_probe():
    """d/d(scale, w) of a linear probe of the output — must agree with
    ``jax.grad`` through the XLA reference (the backward IS the reference's
    vjp, so this checks the custom_vjp wiring end to end)."""
    x = _rand(51, (20, 28), 2.0)
    q, scale = quant.quantize_int8(x)
    w = _rand(52, (28, 12))
    probe = _rand(53, (20, 12))

    def f_pal(s, w_):
        return jnp.sum(probe * ops.dequant_matmul(
            q, s, w_, block_m=16, block_n=16, block_k=16))

    def f_ref(s, w_):
        return jnp.sum(probe * ref.dequant_matmul_ref(q, s, w_))

    gs_p, gw_p = jax.grad(f_pal, argnums=(0, 1))(scale, w)
    gs_r, gw_r = jax.grad(f_ref, argnums=(0, 1))(scale, w)
    _close(gs_p, gs_r)
    _close(gw_p, gw_r)


def test_dqmm_grad_under_jit():
    q, scale = quant.quantize_int8(_rand(61, (16, 16), 2.0))
    w = _rand(62, (16, 16))
    probe = _rand(63, (16, 16))
    g_p = jax.jit(jax.grad(lambda w_: jnp.sum(
        probe * ops.dequant_matmul(q, scale, w_))))(w)
    g_r = jax.grad(lambda w_: jnp.sum(
        probe * ref.dequant_matmul_ref(q, scale, w_)))(w)
    _close(g_p, g_r)


def test_dqmm_vmap_over_cohort():
    """vmap over a leading client axis — the shape the fused round's
    vmap-over-clients lowering would present."""
    K, M, D, H = 3, 10, 14, 6
    qs, scales = [], []
    for i in range(K):
        qi, si = quant.quantize_int8(_rand(70 + i, (M, D), 2.0))
        qs.append(qi)
        scales.append(si)
    q = jnp.stack(qs)
    scale = jnp.stack(scales)
    w = _rand(80, (D, H))
    got = jax.vmap(lambda qq, ss: ops.dequant_matmul(
        qq, ss, w, block_m=8, block_n=8, block_k=8))(q, scale)
    want = jax.vmap(lambda qq, ss: ref.dequant_matmul_ref(qq, ss, w))(q, scale)
    _close(got, want)


def test_tiered_matmul_pallas_vs_xla():
    """``quant.tiered_matmul`` — the quant-aware consumer entry — agrees
    across backends and handles the float-tier ``x_scale=None`` case."""
    x = _rand(91, (18, 26), 2.0)
    q, scale = quant.quantize_int8(x)
    w = _rand(92, (26, 10))
    _close(quant.tiered_matmul(q, scale, w, use_pallas=True),
           quant.tiered_matmul(q, scale, w, use_pallas=False))
    _close(quant.tiered_matmul(x, None, w, use_pallas=True),
           quant.tiered_matmul(x, None, w, use_pallas=False))


# ---------------------------------------------------------------------------
# sparse_cohort_add: forward conformance
# ---------------------------------------------------------------------------


def _sparse_case(seed, K, k, L, weights=None):
    rng = np.random.RandomState(seed)
    idx = jnp.asarray(rng.randint(0, L, size=(K, k)), jnp.int32)
    vals = jnp.asarray(rng.randn(K, k), jnp.float32)
    w = (jnp.asarray(weights, jnp.float32) if weights is not None
         else jnp.asarray(rng.rand(K) + 0.1, jnp.float32))
    return idx, vals, w


def test_sparse_matches_ref_with_duplicates():
    idx, vals, w = _sparse_case(0, K=4, k=7, L=50)
    _close(ops.sparse_cohort_add(idx, vals, w, 50),
           ref.sparse_cohort_add_ref(idx, vals, w, 50))


@settings(max_examples=12, deadline=None)
@given(K=st.integers(1, 6), k=st.integers(1, 32),
       L=st.sampled_from([1, 8, 50, 400]), zero_w=st.booleans())
@example(K=1, k=1, L=1, zero_w=False)
def test_sparse_shape_sweep(K, k, L, zero_w):
    """Hypothesis sweep: duplicate and out-of-order indices arise naturally
    from random draws; ``zero_w`` zeroes one client's Eq. 1 weight (a
    screened-out client must contribute exactly nothing)."""
    k = min(k, L)
    idx, vals, w = _sparse_case(K * 100 + k, K, k, L)
    if zero_w:
        w = w.at[0].set(0.0)
    _close(ops.sparse_cohort_add(idx, vals, w, L),
           ref.sparse_cohort_add_ref(idx, vals, w, L))


def test_sparse_all_clients_same_index():
    """Worst-case collision: every (client, slot) hits one index — the
    serialized read-modify-write loop must accumulate all K*k terms."""
    K, k, L = 5, 9, 30
    idx = jnp.full((K, k), 17, jnp.int32)
    vals = jnp.asarray(np.random.RandomState(1).randn(K, k), jnp.float32)
    w = jnp.asarray(np.random.RandomState(2).rand(K), jnp.float32)
    got = ops.sparse_cohort_add(idx, vals, w, L)
    _close(got, ref.sparse_cohort_add_ref(idx, vals, w, L))
    assert float(jnp.sum(got != 0)) == 1.0


def test_sparse_adversarial_values():
    """Denormals, +-1e30 magnitudes, and exact negatives in one payload."""
    idx = jnp.asarray([[0, 1, 2, 2], [2, 0, 3, 3]], jnp.int32)
    vals = jnp.asarray([[1e-40, 1e30, 5.0, -5.0],
                        [-1e30, 2e-40, 7.5, -7.5]], jnp.float32)
    w = jnp.asarray([1.0, 1.0], jnp.float32)
    _close(ops.sparse_cohort_add(idx, vals, w, 4),
           ref.sparse_cohort_add_ref(idx, vals, w, 4))


def test_sparse_large_length_falls_back_to_ref(monkeypatch):
    """The documented dispatch rule: a dense block too large for VMEM
    residency routes to the XLA scatter reference — bitwise, because the
    fallback IS the reference."""
    idx, vals, w = _sparse_case(5, K=3, k=4, L=64)
    monkeypatch.setattr(sparse_agg, "MAX_VMEM_ELEMS", 32)
    got = ops.sparse_cohort_add(idx, vals, w, 64)
    want = ref.sparse_cohort_add_ref(idx, vals, w, 64)
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_sparse_under_jit():
    idx, vals, w = _sparse_case(6, K=4, k=6, L=40)
    got = jax.jit(lambda i, v, ww: ops.sparse_cohort_add(i, v, ww, 40)
                  )(idx, vals, w)
    _close(got, ref.sparse_cohort_add_ref(idx, vals, w, 40))


# ---------------------------------------------------------------------------
# compression-layer integration
# ---------------------------------------------------------------------------


def test_ingraph_sparse_aggregate_flag_parity():
    idx, vals, w = _sparse_case(7, K=5, k=8, L=100)
    _close(ingraph_sparse_aggregate(idx, vals, w, 100, use_pallas=True),
           ingraph_sparse_aggregate(idx, vals, w, 100, use_pallas=False))


def test_ingraph_compress_leaf_parity():
    """Full leaf pipeline (delta + error feedback -> top-k -> fold): the
    selection and residual math are shared, so idx/vals/residuals must be
    IDENTICAL across backends and only the aggregation differs by
    accumulation noise."""
    K, L = 4, 120
    rng = np.random.RandomState(8)
    start = jnp.asarray(rng.randn(L), jnp.float32)
    end = jnp.asarray(rng.randn(K, L) * 0.1 + np.asarray(start), jnp.float32)
    residual = jnp.asarray(rng.randn(K, L) * 0.01, jnp.float32)
    w = jnp.asarray(rng.rand(K) + 0.1, jnp.float32)
    agg_p, res_p, idx_p, vals_p = ingraph_compress_leaf(
        start, end, residual, w, 0.1, use_pallas=True)
    agg_x, res_x, idx_x, vals_x = ingraph_compress_leaf(
        start, end, residual, w, 0.1, use_pallas=False)
    assert np.array_equal(np.asarray(idx_p), np.asarray(idx_x))
    assert np.array_equal(np.asarray(vals_p), np.asarray(vals_x))
    assert np.array_equal(np.asarray(res_p), np.asarray(res_x))
    _close(agg_p, agg_x)


# ---------------------------------------------------------------------------
# fused-round and server integration (use_pallas=True vs XLA default)
# ---------------------------------------------------------------------------


def _mlp_world(seed=0, K=3, nb=2, B=8, D=12, H=8, C=4):
    rng = np.random.RandomState(seed)
    params = {"w1": jnp.asarray(rng.randn(D, H) * 0.3, jnp.float32),
              "b1": jnp.zeros((H,), jnp.float32),
              "w2": jnp.asarray(rng.randn(H, C) * 0.3, jnp.float32)}
    batches = {"x": jnp.asarray(rng.randn(K, nb, B, D), jnp.float32),
               "y": jnp.asarray(rng.randint(0, C, size=(K, nb, B)), jnp.int32)}
    nb_live = jnp.full((K,), nb, jnp.int32)
    weights = jnp.ones((K,), jnp.float32) / K
    return params, batches, nb_live, weights


def _mlp_loss(params, frozen, state, batch):
    h = jnp.tanh(batch["x"] @ params["w1"] + params["b1"])
    logits = h @ params["w2"]
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)
    return jnp.mean(nll), state


@pytest.mark.parametrize("unroll", [True, False])
def test_fused_compressed_round_pallas_parity(unroll):
    """The tentpole wiring: a compressed fused round with the Pallas cohort
    fold reproduces the XLA scatter round on params, residuals and losses —
    for both the unrolled (CPU) and vmap lowerings."""
    params, batches, nb_live, weights = _mlp_world()
    K = int(nb_live.shape[0])
    residuals = jax.tree.map(
        lambda l: jnp.zeros((K, l.size), jnp.float32), params)

    def run(use_pallas):
        fn = make_fused_round(_mlp_loss, sgd(0.05), compress_ratio=0.3,
                              unroll=unroll, use_pallas=use_pallas)
        return fn(params, {}, {}, batches, nb_live, weights, residuals)

    p_p, _, l_p, r_p = run(True)
    p_x, _, l_x, r_x = run(False)
    _close(l_p, l_x)
    for a, b in zip(jax.tree.leaves(p_p), jax.tree.leaves(p_x)):
        _close(a, b)
    for a, b in zip(jax.tree.leaves(r_p), jax.tree.leaves(r_x)):
        _close(a, b)


def test_quant_aware_int8_round_pallas_parity():
    """int8 tier + quant-aware consumer: the batch keeps (x int8, x_scale)
    and the loss routes its leading GEMM through ``tiered_matmul``; the
    Pallas in-register dequant round must track the materializing XLA
    round across both lowerings."""
    params, batches, nb_live, weights = _mlp_world(seed=1)
    K, nb = batches["x"].shape[:2]
    qs = np.zeros(batches["x"].shape, np.int8)
    ss = np.zeros(batches["x"].shape[:3] + (1,), np.float32)
    for ki in range(K):
        for ni in range(nb):
            qb, sb = quant.quantize_int8(batches["x"][ki, ni])
            qs[ki, ni] = np.asarray(qb)
            ss[ki, ni] = np.asarray(sb)
    qbatches = {"x": jnp.asarray(qs), "x_scale": jnp.asarray(ss),
                "y": batches["y"]}

    def consumer(params, frozen, state, batch):
        h = jnp.tanh(quant.tiered_matmul(
            batch["x"], batch.get("x_scale"), params["w1"],
            use_pallas=batch.get("use_pallas", False)) + params["b1"])
        logp = jax.nn.log_softmax(h @ params["w2"])
        nll = -jnp.take_along_axis(logp, batch["y"][:, None], axis=1)
        return jnp.mean(nll), state

    consumer.consumes_quantized = True

    def run(use_pallas, unroll):
        loss = quant.make_tiered_loss(consumer, "int8",
                                      use_pallas=use_pallas)
        fn = make_fused_round(loss, sgd(0.05), unroll=unroll)
        return fn(params, {}, {}, qbatches, nb_live, weights)

    ref_p, _, ref_l = run(False, True)
    for use_pallas, unroll in [(True, True), (True, False), (False, False)]:
        p, _, losses = run(use_pallas, unroll)
        _close(losses, ref_l)
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(ref_p)):
            _close(a, b)


@pytest.mark.slow
def test_e2e_smartfreeze_two_stage_pallas_trajectory():
    """Acceptance headline: a 2-stage SmartFreeze CNN trajectory with
    compressed uplinks runs entirely through the Pallas cohort fold
    (``SmartFreezeServer(use_pallas=True)``) and stays allclose (f32) to
    the XLA-default twin — params, per-round losses, and uplink bytes."""
    from repro.data.partition import dirichlet_partition
    from repro.data.synthetic import SyntheticVision
    from repro.fl.client import make_client_fleet
    from repro.fl.server import SmartFreezeServer
    from repro.models.cnn import CNN, CNNConfig

    sv = SyntheticVision(num_classes=4, image_size=8)
    train = sv.sample(128, seed=1)
    parts = dirichlet_partition(train["y"], 6, alpha=1.0, seed=0)
    clients = make_client_fleet(train, parts, scenario="low", seed=0)
    cfg = CNNConfig("rn", "resnet", stage_sizes=(1, 1), stage_channels=(4, 8),
                    num_classes=4)
    model = CNN(cfg)
    params, state = model.init(jax.random.PRNGKey(0))

    def run(use_pallas):
        srv = SmartFreezeServer(model, clients, clients_per_round=4,
                                batch_size=16, rounds_per_stage=2,
                                compress_ratio=0.2, seed=0,
                                pace_kwargs=dict(min_rounds=999),
                                use_pallas=use_pallas)
        return srv.run(params, state, total_rounds=4)

    out_p, out_x = run(True), run(False)
    assert len(out_p["history"]) == len(out_x["history"]) == 4
    stages = [r.stage for r in out_p["history"]]
    assert len(set(stages)) >= 2  # the trajectory really crossed a freeze
    for rp, rx in zip(out_p["history"], out_x["history"]):
        assert rp.stage == rx.stage
        assert rp.uplink_bytes == rx.uplink_bytes
        _close(rp.loss, rx.loss, 1e-4)
    for a, b in zip(jax.tree.leaves(out_p["params"]),
                    jax.tree.leaves(out_x["params"])):
        _close(a, b, 1e-4)


# ---------------------------------------------------------------------------
# dispatch plumbing
# ---------------------------------------------------------------------------


def test_interpret_default_is_container_aware():
    """``ops`` wrappers pass ``interpret=None`` -> backend probe: True off
    TPU (this CI container is CPU-only, so the kernel bodies actually
    execute via the Pallas interpreter here), False on real TPUs."""
    want = jax.default_backend() != "tpu"
    assert ops._default_interpret() is want
    assert want is True  # this suite runs on the CPU container


def test_use_pallas_rejects_sharded_mesh():
    """The engine guard: the Pallas cohort fold is single-device; a real
    multi-device client mesh must be refused loudly, not silently wrong."""
    n_dev = jax.device_count()
    if n_dev < 2:
        class _FakeMesh:
            pass
        import repro.fl.engine as eng
        orig = eng.client_axis_size
        eng.client_axis_size = lambda m: 4
        try:
            with pytest.raises(ValueError, match="use_pallas"):
                make_fused_round(_mlp_loss, sgd(0.1), mesh=_FakeMesh(),
                                 use_pallas=True)
        finally:
            eng.client_axis_size = orig
    else:
        from repro.launch.mesh import make_client_mesh
        with pytest.raises(ValueError, match="use_pallas"):
            make_fused_round(_mlp_loss, sgd(0.1),
                             mesh=make_client_mesh(n_dev), use_pallas=True)


@pytest.mark.slow
def test_lm_attention_impl_pallas_matches_xla():
    """``ArchConfig.attention_impl="pallas"`` (the ``--use-pallas`` launch
    route) sends GQA full-sequence attention through the flash kernel; loss
    and grads on a reduced f32 LM must track the XLA attention graph."""
    import dataclasses

    from repro import configs
    from repro.data.synthetic import make_lm_batch
    from repro.models.transformer import build

    base = configs.get("llama3-8b").reduced(num_layers=2)
    base = dataclasses.replace(base, param_dtype="float32",
                               compute_dtype="float32")
    batch = None
    out = {}
    for impl in ("xla", "pallas"):
        cfg = dataclasses.replace(base, attention_impl=impl)
        model = build(cfg)
        params = model.init(jax.random.PRNGKey(0))
        if batch is None:
            batch = {k: jnp.asarray(v)
                     for k, v in make_lm_batch(cfg, 2, 48, 0).items()}
        loss, grads = jax.value_and_grad(
            lambda p: model.loss(p, batch))(params)
        out[impl] = (float(loss), grads)
    assert abs(out["pallas"][0] - out["xla"][0]) <= 1e-5 * max(
        1.0, abs(out["xla"][0]))
    for gp, gx in zip(jax.tree.leaves(out["pallas"][1]),
                      jax.tree.leaves(out["xla"][1])):
        _close(gp, gx, 1e-4)
