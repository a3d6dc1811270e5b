"""Plain reference of a federated round of Granite 4.0-H (a Mamba-2 + GQA
hybrid, ``granitemoehybrid`` without experts), and the inputs and weights
the benchmark makes from its seed.

Written from the model's description alone: it imports nothing of the
program under test. With ``rms`` an RMSNorm with weight and ``r`` the
``residual_multiplier``:

- embedding: ``h = embedding_multiplier * E[tokens]``;
- every layer: ``h = h + r * mixer(rms(h))``, then ``h = h + r *
  mlp(rms(h))``, ``mlp(x) = W_down(silu(W_gate x) * W_up x)``; the mixer
  of each layer is given by the published ``layer_types``;
- attention: grouped queries (each KV head shared by consecutive query
  heads), no biases, no position embedding, ``softmax(attention_multiplier
  * q k^T + causal mask) v``, then ``W_o``;
- Mamba-2: ``[z, xBC, dt] = W_in x``; ``xBC = silu(causal_conv(xBC) + b)``
  split into ``x`` (heads x head width), ``B`` and ``C`` (one group);
  ``dt = softplus(dt + dt_bias)``, ``A = -exp(A_log)``; the state space
  model ``h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T``, ``y_t = h_t C_t +
  D x_t``, computed here in its quadratic (attention-like) form over the
  whole sequence, ``y = (L o C B^T)(dt x) + D x`` with ``L_ts = exp(sum_{s <
  r <= t} dt_r A)`` for ``s <= t``, in blocks of query rows; output
  ``W_out rms(y * silu(z))`` over all ``d_inner`` channels;
- the last stage's head is tied: ``logits = rms(h) E^T / logits_scaling``.

Training follows SmartFreeze (arXiv:2408.09101) as the program's LM zoo
does it: the layers split into ``num_freeze_blocks`` blocks (the first
``L mod T`` one layer longer); at stage ``t`` the embedding and blocks
``< t`` are frozen and run forward only, with no gradient into them; block
``t`` trains (and the embedding at stage 0, through its lookup); layers past
block ``t`` are not there. Departures from the published model, as the
program makes them:

- before the last stage, an output module stands in for the missing
  layers: one slim proxy layer per later block (the model's attention, as
  above, and an MLP of width ``hidden_size``), an RMSNorm and an untied
  head over the vocabulary, the logits divided by ``logits_scaling`` too;
- at the last stage the tied embedding is in the trained tree and trains
  through the head alone (its lookup feeds the frozen layers);
- the vocabulary is the configuration's slice (``vocab_size``), and the
  tokens are drawn from it;
- each position predicts the next token and the last predicts none; the
  loss is the mean cross-entropy over the predicted positions.

Each client runs SGD with global-norm clipping over its minibatches of
token rows; the server takes the dataset-weighted mean (Eq. 1). Inputs: a
client's rows are drawn token by token from its topic's own distribution
over the vocabulary, one of ``TOPICS`` topics per row as the pool's
Dirichlet skew assigns them, so that a model can learn and the loss can
fall.

Everything is float32, and every product runs at ``highest`` precision.
"""
from __future__ import annotations

from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

TOPICS = 16          # token distributions the pool's rows are drawn from
TOPIC_SPREAD = 2.0   # std of a topic's token log-probabilities
QUERY_BLOCK = 256    # query rows per block of the quadratic forms


def tiny(cfg: Dict) -> Dict:
    """``cfg`` cut to a size the CPU runs in seconds: the same layer
    pattern and depth, toy widths, and sequences of ``seq_len`` 16 in four
    SSD chunks (the tests' traffic keeps the real ``seq_len``)."""
    return dict(cfg, hidden_size=8, intermediate_size=16,
                shared_intermediate_size=16, num_attention_heads=2,
                num_key_value_heads=1, mamba_n_heads=2, mamba_d_head=8,
                mamba_d_state=4, mamba_chunk_size=4, vocab_size=32,
                seq_len=16)


def seq_len(cfg: Dict, traffic: Dict) -> int:
    """Tokens in a row: the traffic's, or the tiny configuration's."""
    return cfg.get("seq_len", traffic["seq_len"])


# ---------------------------------------------------------------------------
# the model's layout
# ---------------------------------------------------------------------------


def layer_kinds(cfg: Dict) -> List[str]:
    """``"mamba"`` or ``"attention"`` for each of the layers held."""
    return list(cfg["layer_types"][:cfg["num_hidden_layers"]])


def block_bounds(cfg: Dict) -> List[int]:
    """Layer boundaries of the freeze blocks: ``[0, b_1, ..., L]``."""
    n, t = cfg["num_hidden_layers"], cfg["num_freeze_blocks"]
    base, rem = divmod(n, t)
    bounds = [0]
    for i in range(t):
        bounds.append(bounds[-1] + base + (1 if i < rem else 0))
    return bounds


def runs(cfg: Dict, stage: int) -> List[Tuple[str, str, int, int]]:
    """The stage's runs of layers in execution order, ``(region, kind,
    first, end)``: each maximal stretch of one kind is cut where the frozen
    region ``[0, lo)`` ends and where the trained block ``[lo, hi)`` ends.
    The weights keep each run under its index in this list."""
    bounds = block_bounds(cfg)
    lo, hi = bounds[stage], bounds[stage + 1]
    kinds = layer_kinds(cfg)
    out, start = [], 0
    for i in range(1, len(kinds) + 1):
        if i < len(kinds) and kinds[i] == kinds[start]:
            continue
        for region, a, b in (("frozen", 0, lo), ("active", lo, hi)):
            first, end = max(a, start), min(b, i)
            if first < end:
                out.append((region, kinds[start], first, end))
        start = i
    return out


# ---------------------------------------------------------------------------
# inputs and weights from the seed
# ---------------------------------------------------------------------------


def skew_classes(cfg: Dict) -> int:
    """The topics the pool's label skew is drawn over."""
    return TOPICS


def make_inputs(cfg: Dict, traffic: Dict, labels: jnp.ndarray, key, chunk
                ) -> jnp.ndarray:
    """Token rows for ``labels`` (each row's topic), the ``chunk``-th slice
    of the pool: each token drawn independently from the topic's
    distribution (the same in every chunk), [n, seq_len] int32."""
    vocab = cfg["vocab_size"]
    k_topic, k_tok = jax.random.split(key)
    logp = TOPIC_SPREAD * jax.random.normal(k_topic, (TOPICS, vocab),
                                            jnp.float32)
    cdf = jnp.cumsum(jax.nn.softmax(logp, axis=-1), axis=-1)
    u = jax.random.uniform(jax.random.fold_in(k_tok, chunk),
                           (labels.shape[0], seq_len(cfg, traffic)))
    tok = jax.vmap(jnp.searchsorted)(cdf[labels], u * cdf[labels][:, -1:])
    return jnp.minimum(tok, vocab - 1).astype(jnp.int32)


def _dense(key, d_in, d_out):
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32)
            / np.sqrt(d_in)}


def _norm(d):
    return {"scale": jnp.ones((d,), jnp.float32)}


def _mlp_init(key, d, width):
    k1, k2, k3 = jax.random.split(key, 3)
    return {"gate": _dense(k1, d, width), "up": _dense(k2, d, width),
            "down": _dense(k3, width, d)}


def _attention_layer(cfg, key, width):
    d, hd = cfg["hidden_size"], _head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    ks = jax.random.split(key, 5)
    return {"ln1": _norm(d),
            "attn": {"wq": _dense(ks[0], d, nq * hd),
                     "wk": _dense(ks[1], d, nkv * hd),
                     "wv": _dense(ks[2], d, nkv * hd),
                     "wo": _dense(ks[3], nq * hd, d)},
            "ln2": _norm(d), "mlp": _mlp_init(ks[4], d, width)}


def _mamba_layer(cfg, key):
    d = cfg["hidden_size"]
    di, n, heads = _d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_n_heads"]
    conv_ch, k = di + 2 * n, cfg["mamba_d_conv"]
    ks = jax.random.split(key, 7)
    # A in [1, 16] and dt in [1e-3, 1e-1] log-uniform, as Mamba-2 starts
    a = jax.random.uniform(ks[0], (heads,), jnp.float32, 1.0, 16.0)
    dt = jnp.exp(jax.random.uniform(ks[1], (heads,), jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return {"ln1": _norm(d),
            "mix": {"in_proj": _dense(ks[2], d, 2 * di + 2 * n + heads),
                    "conv": {"w": jax.random.normal(ks[3], (k, conv_ch),
                                                    jnp.float32) / np.sqrt(k),
                             "b": 0.1 * jax.random.normal(ks[4], (conv_ch,),
                                                          jnp.float32)},
                    "A_log": jnp.log(a),
                    "D": jnp.ones((heads,), jnp.float32),
                    "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
                    "norm": _norm(di),
                    "out_proj": _dense(ks[5], di, d)},
            "ln2": _norm(d),
            "mlp": _mlp_init(ks[6], d, cfg["shared_intermediate_size"])}


def _stack(layers):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init_weights(cfg: Dict, stage: int, key):
    """(frozen, active, state) for ``stage``; ``state`` is empty. Call
    under ``jax.jit`` with ``cfg`` and ``stage`` static: one program makes
    every leaf on the device."""
    d, vocab = cfg["hidden_size"], cfg["vocab_size"]
    t = cfg["num_freeze_blocks"]
    k_embed, k_layers, k_op = jax.random.split(key, 3)
    frozen: Dict = {"runs": {}}
    active: Dict = {"runs": {}}
    final = stage == t - 1
    embed = 0.02 * jax.random.normal(k_embed, (vocab, d), jnp.float32)
    (active if stage == 0 or final else frozen)["embed"] = embed
    for ri, (region, kind, first, end) in enumerate(runs(cfg, stage)):
        layers = [(_mamba_layer(cfg, jax.random.fold_in(k_layers, i))
                   if kind == "mamba" else
                   _attention_layer(cfg, jax.random.fold_in(k_layers, i),
                                    cfg["shared_intermediate_size"]))
                  for i in range(first, end)]
        (frozen if region == "frozen" else active)["runs"][str(ri)] = (
            _stack(layers))
    if final:
        active["final_norm"] = _norm(d)
    else:
        k_proxy, k_head = jax.random.split(k_op)
        op = {"norm": _norm(d), "head": _dense(k_head, d, vocab)}
        n_proxy = t - stage - 1
        if n_proxy:
            op["proxy"] = _stack([_attention_layer(
                cfg, jax.random.fold_in(k_proxy, i), d)
                for i in range(n_proxy)])
        active["op"] = op
    return frozen, active, {}


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _d_inner(cfg):
    return cfg["mamba_expand"] * cfg["hidden_size"]


def _rms(p, x, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * p["scale"]


def _mlp(p, x):
    gate = x @ p["gate"]["w"]
    return (jax.nn.silu(gate) * (x @ p["up"]["w"])) @ p["down"]["w"]


def _query_blocks(s: int) -> int:
    b = min(QUERY_BLOCK, s)
    while s % b:
        b -= 1
    return b


def _by_query_blocks(fn, s: int):
    """``fn(t0, rows)`` over blocks of ``rows`` query positions starting at
    ``t0``, each block recomputed in the backward pass; the blocks'
    outputs [n, B, rows, ...] joined along the sequence."""
    rows = _query_blocks(s)
    block = jax.checkpoint(lambda t0: fn(t0, rows))
    out = jax.lax.map(block, jnp.arange(0, s, rows))
    return jnp.moveaxis(out, 0, 1).reshape((out.shape[1], s) + out.shape[3:])


def _attention(cfg, p, x):
    b, s, _ = x.shape
    nq, nkv, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                   _head_dim(cfg))
    q = (x @ p["wq"]["w"]).reshape(b, s, nq, hd)
    k = jnp.repeat((x @ p["wk"]["w"]).reshape(b, s, nkv, hd), nq // nkv, 2)
    v = jnp.repeat((x @ p["wv"]["w"]).reshape(b, s, nkv, hd), nq // nkv, 2)
    scale = cfg["attention_multiplier"]

    def rows(t0, n):
        qb = jax.lax.dynamic_slice_in_dim(q, t0, n, axis=1)
        scores = scale * jnp.einsum("bqhd,bshd->bhqs", qb, k)
        causal = (t0 + jnp.arange(n))[:, None] >= jnp.arange(s)[None, :]
        probs = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqs,bshd->bqhd", probs, v)

    out = _by_query_blocks(rows, s)
    return out.reshape(b, s, nq * hd) @ p["wo"]["w"]


def _causal_conv(p, x):
    k = p["w"].shape[0]
    pad = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    return sum(pad[:, i:i + x.shape[1]] * p["w"][i] for i in range(k)) + p["b"]


def ssd_quadratic(x, dt, a, bm, cm):
    """The state space model's output without the skip term, in its
    quadratic form: ``y_t = sum_{s <= t} exp(sum_{s < r <= t} dt_r a) (C_t .
    B_s) dt_s x_s``. x: [B, S, H, P]; dt: [B, S, H]; a: [H]; bm, cm: [B, S,
    N] (one group). Returns [B, S, H, P]."""
    s = x.shape[1]
    cum = jnp.cumsum(dt * a, axis=1)                      # [B, S, H]
    xdt = x * dt[..., None]

    def rows(t0, n):
        c_t = jax.lax.dynamic_slice_in_dim(cum, t0, n, axis=1)
        seg = c_t[:, :, None, :] - cum[:, None, :, :]     # [B, n, S, H]
        causal = (t0 + jnp.arange(n))[:, None] >= jnp.arange(s)[None, :]
        decay = jnp.exp(jnp.where(causal[None, :, :, None], seg, -jnp.inf))
        cb = jnp.einsum("bqn,bsn->bqs",
                        jax.lax.dynamic_slice_in_dim(cm, t0, n, axis=1), bm)
        return jnp.einsum("bqsh,bqs,bshp->bqhp", decay, cb, xdt)

    return _by_query_blocks(rows, s)


def _mamba(cfg, p, u):
    b, s, _ = u.shape
    di, n, heads = _d_inner(cfg), cfg["mamba_d_state"], cfg["mamba_n_heads"]
    zxbcdt = u @ p["in_proj"]["w"]
    z, xbc, dt = (zxbcdt[..., :di], zxbcdt[..., di:2 * di + 2 * n],
                  zxbcdt[..., 2 * di + 2 * n:])
    xbc = jax.nn.silu(_causal_conv(p["conv"], xbc))
    x = xbc[..., :di].reshape(b, s, heads, cfg["mamba_d_head"])
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + p["dt_bias"])
    y = ssd_quadratic(x, dt, -jnp.exp(p["A_log"]), bm, cm)
    y = (y + p["D"][:, None] * x).reshape(b, s, di)
    y = _rms(p["norm"], y * jax.nn.silu(z), cfg["rms_norm_eps"])
    return y @ p["out_proj"]["w"]


def _layer(cfg, kind, p, h):
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    mix = (_mamba(cfg, p["mix"], _rms(p["ln1"], h, eps)) if kind == "mamba"
           else _attention(cfg, p["attn"], _rms(p["ln1"], h, eps)))
    h = h + r * mix
    return h + r * _mlp(p["mlp"], _rms(p["ln2"], h, eps))


def _run_layers(cfg, kind, stacked, h):
    n = jax.tree.leaves(stacked)[0].shape[0]
    for i in range(n):
        h = _layer(cfg, kind, jax.tree.map(lambda x: x[i], stacked), h)
    return h


def loss(cfg: Dict, stage: int, frozen, active, tokens) -> jnp.ndarray:
    """Mean next-token cross-entropy of the stage-``stage`` submodel on
    ``tokens`` [B, S] (the last position predicts nothing)."""
    final = stage == cfg["num_freeze_blocks"] - 1
    embed = (active if "embed" in active else frozen)["embed"]
    h = cfg["embedding_multiplier"] * embed[tokens]
    plan = runs(cfg, stage)
    for ri, (region, kind, _, _) in enumerate(plan):
        if region == "frozen":
            h = _run_layers(cfg, kind, frozen["runs"][str(ri)], h)
    if any(region == "frozen" for region, *_ in plan):
        h = jax.lax.stop_gradient(h)
    for ri, (region, kind, _, _) in enumerate(plan):
        if region == "active":
            h = _run_layers(cfg, kind, active["runs"][str(ri)], h)
    eps = cfg["rms_norm_eps"]
    if final:
        logits = _rms(active["final_norm"], h, eps) @ active["embed"].T
    else:
        op = active["op"]
        if "proxy" in op:
            h = _run_layers(cfg, "attention", op["proxy"], h)
        logits = _rms(op["norm"], h, eps) @ op["head"]["w"]
    logits = logits[:, :-1] / cfg["logits_scaling"]
    gold = jnp.take_along_axis(logits, tokens[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


# ---------------------------------------------------------------------------
# one federated round
# ---------------------------------------------------------------------------


def batch_plan(n: int, batch: int, epochs: int, seed: int) -> np.ndarray:
    """A client's minibatch indices for one round: a fresh permutation per
    epoch from ``RandomState(seed)``, cut into whole batches (the last
    partial batch is dropped). [steps, batch]."""
    rng = np.random.RandomState(seed)
    plan = []
    for _ in range(epochs):
        order = rng.permutation(n)
        plan += [order[i:i + batch] for i in range(0, n - batch + 1, batch)]
    return np.stack(plan)


def round_seed(client_seed: int, round_idx: int) -> int:
    """The seed of a client's batch order in round ``round_idx``."""
    return client_seed * 99991 + round_idx


class Reference:
    """The round at float32, every product at ``highest`` precision."""

    def __init__(self, cfg: Dict, stage: int, *, lr: float, clip_norm: float):
        self.cfg, self.stage = cfg, stage
        self._local = jax.jit(self._local_train)
        self._fold = jax.jit(self._fold_trees)
        self.lr, self.clip_norm = lr, clip_norm

    def _precision(self):
        return jax.default_matmul_precision("highest")

    def _local_train(self, frozen, active, state, xs, ys):
        cfg, stage = self.cfg, self.stage

        def step(carry, tokens):
            a, lsum = carry
            value, g = jax.value_and_grad(
                lambda p: loss(cfg, stage, frozen, p, tokens))(a)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, self.clip_norm / (norm + 1e-9))
            a = jax.tree.map(lambda p, d: p - self.lr * scale * d, a, g)
            return (a, lsum + value), None

        (a, lsum), _ = jax.lax.scan(step, (active, 0.0), xs)
        return a, state, lsum / xs.shape[0]

    def _fold_trees(self, trees, w):
        return jax.tree.map(
            lambda *xs: sum(wi * x for wi, x in zip(w, xs)), *trees)

    def clients(self, frozen, active, state, data: Sequence[Dict],
                plans: Sequence[np.ndarray]) -> List:
        """Each client's local training from (active, state): ``data[i]``
        is client i's {"x": token rows, "y": topics} (host arrays),
        ``plans[i]`` its batch plan. Returns ``[(active, state, mean loss),
        ...]``."""
        out = []
        with self._precision():
            for d, plan in zip(data, plans):
                xs = jnp.asarray(d["x"][plan])
                ys = jnp.asarray(d["y"][plan])
                a, s, value = self._local(frozen, active, state, xs, ys)
                out.append((a, s, float(value)))
        return out

    def fold(self, outs: Sequence, weights: Sequence[float]):
        """Eq. 1: the dataset-weighted mean of the clients' (active,
        state), as float32 host arrays."""
        w = np.asarray(weights, np.float64)
        w = jnp.asarray(w / w.sum(), jnp.float32)
        with self._precision():
            a, s = self._fold([(a, s) for a, s, _ in outs], w)
        return jax.tree.map(lambda x: np.asarray(x, np.float32), (a, s))

    def round(self, frozen, active, state, data: Sequence[Dict],
              plans: Sequence[np.ndarray], weights: Sequence[float]):
        """One round: (active, state) folded over the cohort, and the
        per-client mean losses."""
        outs = self.clients(frozen, active, state, data, plans)
        a, s = self.fold(outs, weights)
        return a, s, [value for *_, value in outs]
