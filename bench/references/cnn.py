"""Plain reference of the CNN testbed's federated round, and the inputs and
weights the benchmark makes from its seed.

Written from the model's description alone: it imports nothing of the
program under test. Models: CIFAR ResNet (He et al., arXiv:1512.03385; 3x3
stem, basic blocks, 1x1 projection shortcut where the width changes) and
VGG with batch norm (Simonyan & Zisserman, arXiv:1409.1556, config D with
BN; max-pool after each stage, one linear head). Training follows
SmartFreeze (arXiv:2408.09101): at stage ``s`` the stem and stages ``< s``
are frozen and run in eval mode, stage ``s`` trains with batch-statistic
batch norm, and an output module of one stride-2 conv per later stage plus
global pooling and a linear head stands in for the rest (the last stage
uses the model's own head). Each client runs SGD with global-norm clipping
over its minibatches; the server takes the dataset-weighted mean (Eq. 1)
of the clients' params and batch-norm running stats.

Parameter and state trees use the layout the round engine consumes:
``frozen``/``active`` params and one batch-norm ``state`` tree for the
whole model.

Everything is float32, and every product runs at ``highest`` precision.
"""
from __future__ import annotations

from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BN_EPS = 1e-5
NOISE = 0.35        # pixel noise around each class prototype
PROTO_CELLS = 8     # prototypes are 8x8 patterns, upsampled to the image


# ---------------------------------------------------------------------------
# inputs and weights from the seed
# ---------------------------------------------------------------------------


def make_images(cfg: Dict, labels: jnp.ndarray, key, chunk) -> jnp.ndarray:
    """Images for ``labels``, the ``chunk``-th slice of the pool: a
    low-frequency prototype per class (the same in every chunk) plus
    Gaussian pixel noise, [n, size, size, channels] float32."""
    size, ch = cfg["image_size"], cfg["in_channels"]
    k_proto, k_noise = jax.random.split(key)
    k_noise = jax.random.fold_in(k_noise, chunk)
    base = jax.random.normal(k_proto, (cfg["num_classes"], PROTO_CELLS,
                                       PROTO_CELLS, ch), jnp.float32)
    rep = max(size // PROTO_CELLS, 1)
    protos = jnp.repeat(jnp.repeat(base, rep, axis=1), rep, axis=2)
    protos = protos[:, :size, :size, :]
    noise = jax.random.normal(k_noise, (labels.shape[0], size, size, ch),
                              jnp.float32)
    return protos[labels] + NOISE * noise


def _conv_init(key, k, c_in, c_out, bias):
    w = jax.random.normal(key, (k, k, c_in, c_out), jnp.float32)
    p = {"w": w * np.sqrt(2.0 / (k * k * c_in))}
    if bias:
        p["b"] = jnp.zeros((c_out,), jnp.float32)
    return p


def _bn_init(key, c):
    k1, k2 = jax.random.split(key)
    params = {"scale": jnp.ones((c,), jnp.float32),
              "bias": jnp.zeros((c,), jnp.float32)}
    # running stats as a trained model would hold them, not the (0, 1) of
    # a fresh one, so that eval-mode batch norm is not the identity
    state = {"mean": 0.1 * jax.random.normal(k1, (c,), jnp.float32),
             "var": jax.random.uniform(k2, (c,), jnp.float32, 0.5, 1.5)}
    return params, state


def _dense_init(key, d_in, d_out):
    return {"w": jax.random.normal(key, (d_in, d_out), jnp.float32)
            / np.sqrt(d_in),
            "b": jnp.zeros((d_out,), jnp.float32)}


def _block_in_channels(cfg: Dict, i: int, j: int) -> int:
    if j > 0:
        return cfg["stage_channels"][i]
    if i > 0:
        return cfg["stage_channels"][i - 1]
    return (cfg["stage_channels"][0] if cfg["kind"] == "resnet"
            else cfg["in_channels"])


def init_weights(cfg: Dict, stage: int, key):
    """(frozen, active, state) for ``stage``. Call under ``jax.jit`` with
    ``cfg`` and ``stage`` static: one program makes every leaf on the
    device."""
    n = len(cfg["stage_sizes"])
    keys = iter(jax.random.split(key, 256))
    resnet = cfg["kind"] == "resnet"
    frozen: Dict = {"stages": {}}
    active: Dict = {"stages": {}}
    state: Dict = {"stages": {}}
    if resnet:
        c0 = cfg["stage_channels"][0]
        bn_p, state["stem_bn"] = _bn_init(next(keys), c0)
        stem = {"conv": _conv_init(next(keys), 3, cfg["in_channels"], c0,
                                   False), "bn": bn_p}
        (active if stage == 0 else frozen)["stem"] = stem
    for i in range(n):
        ch = cfg["stage_channels"][i]
        blocks, bstates = {}, {}
        for j in range(cfg["stage_sizes"][i]):
            c_in = _block_in_channels(cfg, i, j)
            if resnet:
                p, s = {}, {}
                p["conv1"] = _conv_init(next(keys), 3, c_in, ch, False)
                p["bn1"], s["bn1"] = _bn_init(next(keys), ch)
                p["conv2"] = _conv_init(next(keys), 3, ch, ch, False)
                p["bn2"], s["bn2"] = _bn_init(next(keys), ch)
                if c_in != ch:
                    p["proj"] = _conv_init(next(keys), 1, c_in, ch, False)
                    p["bn_proj"], s["bn_proj"] = _bn_init(next(keys), ch)
            else:
                p = {"conv": _conv_init(next(keys), 3, c_in, ch, True)}
                p["bn"], s0 = _bn_init(next(keys), ch)
                s = {"bn": s0}
            blocks[f"b{j}"], bstates[f"b{j}"] = p, s
        state["stages"][f"stage{i}"] = bstates
        if i < stage:
            frozen["stages"][f"stage{i}"] = blocks
        elif i == stage:
            active["stages"][f"stage{i}"] = blocks
    if stage == n - 1:
        active["fc"] = _dense_init(next(keys), cfg["stage_channels"][-1],
                                   cfg["num_classes"])
    else:
        convs, c_in = {}, cfg["stage_channels"][stage]
        for i in range(stage + 1, n):
            convs[f"c{i}"] = _conv_init(next(keys), 3, c_in,
                                        cfg["stage_channels"][i], True)
            c_in = cfg["stage_channels"][i]
        active["op"] = {"convs": convs,
                        "fc": _dense_init(next(keys), c_in,
                                          cfg["num_classes"])}
    return frozen, active, state


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------


def _conv(p, x, stride=1):
    y = jax.lax.conv_general_dilated(x, p["w"], (stride, stride), "SAME",
                                     dimension_numbers=("NHWC", "HWIO",
                                                        "NHWC"))
    return y + p["b"] if "b" in p else y


def _bn(p, s, x, train, momentum):
    if train:
        mean = jnp.mean(x, axis=(0, 1, 2))
        var = jnp.var(x, axis=(0, 1, 2))
        new = {"mean": momentum * s["mean"] + (1 - momentum) * mean,
               "var": momentum * s["var"] + (1 - momentum) * var}
    else:
        mean, var, new = s["mean"], s["var"], s
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS)
    return y * p["scale"] + p["bias"], new


def _resnet_block(p, s, x, stride, train, m):
    new = {}
    h = _conv(p["conv1"], x, stride)
    h, new["bn1"] = _bn(p["bn1"], s["bn1"], h, train, m)
    h = jax.nn.relu(h)
    h = _conv(p["conv2"], h)
    h, new["bn2"] = _bn(p["bn2"], s["bn2"], h, train, m)
    if "proj" in p:
        sc = _conv(p["proj"], x, stride)
        sc, new["bn_proj"] = _bn(p["bn_proj"], s["bn_proj"], sc, train, m)
    else:
        sc = x[:, ::stride, ::stride, :]
    return jax.nn.relu(h + sc), new


def _stage(cfg, i, blocks, bstates, h, train):
    m = cfg["bn_momentum"]
    new = {}
    for j in range(cfg["stage_sizes"][i]):
        p, s = blocks[f"b{j}"], bstates[f"b{j}"]
        if cfg["kind"] == "resnet":
            stride = 2 if (j == 0 and i > 0) else 1
            h, new[f"b{j}"] = _resnet_block(p, s, h, stride, train, m)
        else:
            h = _conv(p["conv"], h)
            h, bn = _bn(p["bn"], s["bn"], h, train, m)
            h = jax.nn.relu(h)
            new[f"b{j}"] = {"bn": bn}
    if cfg["kind"] == "vgg":
        h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 2, 2, 1),
                                  (1, 2, 2, 1), "VALID")
    return h, new


def _head(p, h):
    return jnp.mean(h, axis=(1, 2)) @ p["w"] + p["b"]


def forward(cfg: Dict, stage: int, frozen, active, state, x):
    """Logits of the stage-``stage`` submodel and its new batch-norm state
    (only the trained stage's running stats move)."""
    n = len(cfg["stage_sizes"])
    m = cfg["bn_momentum"]
    new_state = {k: v for k, v in state.items()}
    new_state["stages"] = dict(state["stages"])
    h = x
    if cfg["kind"] == "resnet":
        stem = (active if stage == 0 else frozen)["stem"]
        h = _conv(stem["conv"], h)
        h, bn = _bn(stem["bn"], state["stem_bn"], h, stage == 0, m)
        h = jax.nn.relu(h)
        if stage == 0:
            new_state["stem_bn"] = bn
    for i in range(stage):
        h, _ = _stage(cfg, i, frozen["stages"][f"stage{i}"],
                      state["stages"][f"stage{i}"], h, False)
    name = f"stage{stage}"
    h, new_state["stages"][name] = _stage(cfg, stage, active["stages"][name],
                                          state["stages"][name], h, True)
    if stage == n - 1:
        return _head(active["fc"], h), new_state
    for i in range(stage + 1, n):
        h = jax.nn.relu(_conv(active["op"]["convs"][f"c{i}"], h, 2))
    return _head(active["op"]["fc"], h), new_state


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

        def loss_fn(a, s, x, y):
            logits, new_s = forward(cfg, stage, frozen, a, s, x)
            gold = jnp.take_along_axis(logits, y[:, None], axis=-1)[:, 0]
            return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold), new_s

        def step(carry, batch):
            a, s, lsum = carry
            (loss, new_s), g = jax.value_and_grad(loss_fn, has_aux=True)(
                a, s, *batch)
            norm = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                                for x in jax.tree.leaves(g)))
            scale = jnp.minimum(1.0, self.clip_norm / (norm + 1e-9))
            a = jax.tree.map(lambda p, d: p - self.lr * scale * d, a, g)
            return (a, new_s, lsum + loss), None

        (a, s, lsum), _ = jax.lax.scan(step, (active, state, 0.0), (xs, ys))
        return a, s, lsum / xs.shape[0]

    def _fold_trees(self, trees, w):
        return jax.tree.map(
            lambda *xs: sum(wi * x for wi, x in zip(w, xs)), *trees)

    def clients(self, frozen, active, state, data: Sequence[Dict],
                plans: Sequence[np.ndarray]) -> List:
        """Each client's local training from (active, state): ``data[i]``
        is client i's {"x", "y"} (host arrays), ``plans[i]`` its batch plan.
        Returns ``[(active, state, mean loss), ...]``."""
        out = []
        with self._precision():
            for d, plan in zip(data, plans):
                xs = jnp.asarray(d["x"][plan], jnp.float32)
                ys = jnp.asarray(d["y"][plan])
                a, s, loss = self._local(frozen, active, state, xs, ys)
                out.append((a, s, float(loss)))
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
        return a, s, [loss for *_, loss in outs]
