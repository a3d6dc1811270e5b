"""A configuration, a traffic mix, a cell and a per-layer metric are added
as new files and entries only, and the harness runs them: no file the
benchmark already has is edited."""
import hashlib
import json

from conftest import ROOT, run_cli


def _digests(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file()
            and "__pycache__" not in p.parts and ".trace" not in p.parts}


def test_new_cell_and_metric_from_files_alone(checkout):
    before = _digests(checkout)
    bench = checkout / "bench"
    (bench / "configs" / "resnet10.json").write_text(json.dumps({
        "name": "resnet10", "family": "cnn", "kind": "resnet",
        "stage_sizes": [1, 1, 1, 1], "stage_channels": [4, 8, 8, 8],
        "num_freeze_blocks": 4, "in_channels": 3, "image_size": 8,
        "num_classes": 4, "bn_momentum": 0.6}))
    (bench / "traffic" / "s1.fp16cache.k3.json").write_text(json.dumps({
        "stage": 1, "cache_tier": "fp16", "clients": 9,
        "samples_per_client": 32, "alpha": 0.5, "cohort": 3, "batch": 8,
        "epochs": 1, "lr": 0.05, "clip_norm": 10.0, "lead_rounds": 3}))
    (bench / "limits" / "resnet10.s1.fp16cache.k3.json").write_text(
        json.dumps({"grad_gap": 0.05, "change_gap": 0.05}))
    (bench / "metrics" / "traced_rounds.py").write_text(
        "def read(ctx):\n    return len(ctx['trace'].rounds())\n")
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "resnet10", "source": "https://arxiv.org/abs/1512.03385",
        "file": "bench/configs/resnet10.json", "reduced": [],
        "why": "test configuration"})
    spec["workloads"].append({
        "name": "resnet10.s1.fp16cache.k3", "config": "resnet10",
        "traffic": "s1.fp16cache.k3", "chips": 1, "why": "test cell"})
    spec["per_layer"].append({
        "name": "traced_rounds", "unit": "count", "better": "higher",
        "source": "host_clock", "layer": "engine", "moves": "samples_per_s",
        "workloads": ["resnet10.s1.fp16cache.k3"]})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    for trace in (0, 1):
        rc, out, err = run_cli(checkout, [
            "--workload", "resnet10.s1.fp16cache.k3", "--seed", "3",
            "--seconds", "1", "--trace", str(trace)])
        assert rc == 0, "\n".join(err[-40:])
        line = json.loads(out[-1])
        assert line["correct"] is True, line["checks"]
        if trace:
            assert line["metrics"]["traced_rounds"]["value"] >= 1
        else:
            assert "samples_per_s" in line["metrics"]

    after = _digests(checkout)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {
        p.relative_to(checkout) for p in (
            bench / "configs" / "resnet10.json",
            bench / "traffic" / "s1.fp16cache.k3.json",
            bench / "limits" / "resnet10.s1.fp16cache.k3.json",
            bench / "metrics" / "traced_rounds.py")}


# a toy token family: an embedding and one dense layer over a vocabulary of
# 32, next-token loss on sequences of 8, trained through the real
# RoundEngine on int32 tokens
TOY_DRIVER = '''
"""The toy token family's system under test: the program's RoundEngine."""
import jax
import jax.numpy as jnp

from repro.fl.client import SimClient
from repro.fl.engine import RoundEngine
from repro.optim import sgd


def loss_fn(active, frozen, state, batch):
    x = batch["x"]
    logits = active["embed"][x[:, :-1]] @ active["out"]["w"] + active["out"]["b"]
    gold = jnp.take_along_axis(logits, x[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold), state


def check_layout(cfg, stage, frozen, active, state):
    v, d = cfg["vocab"], cfg["d_model"]
    want = {"embed": (v, d), "out": {"b": (v,), "w": (d, v)}}
    if jax.tree.map(jnp.shape, active) != want or frozen or state:
        raise ValueError("toy weights do not match the layout")


class System:
    def __init__(self, cfg, traffic, frozen, state, pool_x, pool_y, seeds,
                 mesh=None, compute_dtype=None):
        self.engine = RoundEngine(
            loss_fn=loss_fn, optimizer=sgd(traffic["lr"]), frozen=frozen,
            batch_size=traffic["batch"], local_epochs=traffic["epochs"],
            clip_norm=traffic["clip_norm"], mesh=mesh,
            compute_dtype=compute_dtype)
        self.clients = {
            i: SimClient(client_id=i, data={"x": pool_x[i], "y": pool_y[i]},
                         memory_bytes=0.0, capability=1.0, seed=seeds[i])
            for i in range(len(pool_x))}

    def fill_cache(self):
        return 0

    def run_round(self, cohort, round_idx, params, state):
        p, s, losses = self.engine.run_round(self.clients, cohort, params,
                                             state, round_idx)
        return p, s, [losses[c] for c in cohort]

    def close(self):
        self.engine = self.clients = None
'''

TOY_REFERENCE = '''
"""The toy token family's plain reference: float32 jax.numpy, every
product at highest precision."""
import jax
import jax.numpy as jnp
import numpy as np


def tiny(cfg):
    return cfg


def skew_classes(cfg):
    return cfg["topics"]


def make_inputs(cfg, traffic, labels, key, chunk):
    k_topic, k_tok = jax.random.split(key)
    topic_logits = 2.0 * jax.random.normal(k_topic,
                                           (cfg["topics"], cfg["vocab"]))
    return jax.random.categorical(
        jax.random.fold_in(k_tok, chunk), topic_logits[labels][:, None, :],
        shape=(labels.shape[0], traffic["seq_len"])).astype(jnp.int32)


def init_weights(cfg, stage, key):
    v, d = cfg["vocab"], cfg["d_model"]
    k1, k2 = jax.random.split(key)
    active = {"embed": jax.random.normal(k1, (v, d)),
              "out": {"w": jax.random.normal(k2, (d, v)) / np.sqrt(d),
                      "b": jnp.zeros((v,))}}
    return {}, active, {}


def batch_plan(n, batch, epochs, seed):
    rng = np.random.RandomState(seed)
    plan = []
    for _ in range(epochs):
        order = rng.permutation(n)
        plan += [order[i:i + batch] for i in range(0, n - batch + 1, batch)]
    return np.stack(plan)


def round_seed(client_seed, round_idx):
    return client_seed * 99991 + round_idx


def _loss(a, x):
    h = a["embed"][x[:, :-1]]
    logits = jnp.einsum("bsd,dv->bsv", h, a["out"]["w"]) + a["out"]["b"]
    gold = jnp.take_along_axis(logits, x[:, 1:, None], axis=-1)[..., 0]
    return jnp.mean(jax.nn.logsumexp(logits, axis=-1) - gold)


class Reference:
    def __init__(self, cfg, stage, *, lr, clip_norm):
        self.lr, self.clip_norm = lr, clip_norm

    def round(self, frozen, active, state, data, plans, weights):
        outs, losses = [], []
        with jax.default_matmul_precision("highest"):
            for d, plan in zip(data, plans):
                a, total = active, 0.0
                for idx in plan:
                    loss, g = jax.value_and_grad(_loss)(
                        a, jnp.asarray(d["x"][idx]))
                    norm = jnp.sqrt(sum(jnp.sum(x * x)
                                        for x in jax.tree.leaves(g)))
                    scale = jnp.minimum(1.0, self.clip_norm / (norm + 1e-9))
                    a = jax.tree.map(lambda p, q: p - self.lr * scale * q,
                                     a, g)
                    total += float(loss)
                outs.append(a)
                losses.append(total / len(plan))
            w = np.asarray(weights, np.float64)
            w = w / w.sum()
            folded = jax.tree.map(
                lambda *xs: sum(float(wi) * x for wi, x in zip(w, xs)),
                *outs)
        return jax.tree.map(np.asarray, folded), state, losses
'''

TOY_COUNTS = '''
"""The toy token family's counted FLOPs: the dense layer at each of a
sequence's seq_len - 1 predicted positions, times 3."""
from bench.flops import samples_per_round


def flops_per_round(cfg, traffic):
    per_seq = 3 * 2 * cfg["d_model"] * cfg["vocab"] * (traffic["seq_len"] - 1)
    return float(per_seq * samples_per_round(traffic))
'''


def test_new_family_from_files_alone(checkout):
    """A model family that reads no image: inputs, label skew, weights,
    reference and counted FLOPs all come from its own new files."""
    before = _digests(checkout)
    bench = checkout / "bench"
    new = {
        bench / "configs" / "toylm.json": json.dumps({
            "name": "toylm", "family": "toylm", "vocab": 32, "d_model": 16,
            "topics": 4}),
        bench / "traffic" / "tok8.k2.json": json.dumps({
            "stage": 0, "seq_len": 8, "clients": 6, "samples_per_client": 32,
            "alpha": 0.5, "cohort": 2, "batch": 8, "epochs": 1, "lr": 0.5,
            "clip_norm": 10.0, "lead_rounds": 3}),
        bench / "limits" / "toylm.tok8.k2.json": json.dumps({
            "loss_gap": 1e-4, "grad_gap": 1e-4, "change_gap": 1e-4,
            "grad_diff": 1e-4, "change_diff": 1e-4}),
        bench / "drivers" / "toylm.py": TOY_DRIVER,
        bench / "references" / "toylm.py": TOY_REFERENCE,
        (bench / "counts" / "toylm.py"): TOY_COUNTS,
    }
    for path, text in new.items():
        path.write_text(text)
    spec = json.loads((checkout / "BENCHMARK.json").read_text())
    spec["configs"].append({
        "name": "toylm", "source": "https://arxiv.org/abs/1706.03762",
        "file": "bench/configs/toylm.json", "reduced": [],
        "why": "test configuration"})
    spec["workloads"].append({
        "name": "toylm.tok8.k2", "config": "toylm", "traffic": "tok8.k2",
        "chips": 1, "why": "test cell"})
    (checkout / "BENCHMARK.json").write_text(json.dumps(spec))

    for trace in (0, 1):
        rc, out, err = run_cli(checkout, [
            "--workload", "toylm.tok8.k2", "--seed", "2147483911",
            "--seconds", "1", "--trace", str(trace)])
        assert rc == 0, "\n".join(err[-40:])
        line = json.loads(out[-1])
        assert line["correct"] is True, line["checks"]
        if trace:
            assert line["metrics"]["round_mfu"]["value"] > 0
        else:
            assert "samples_per_s" in line["metrics"]

    after = _digests(checkout)
    assert {k: after[k] for k in before} == before
    assert set(after) - set(before) == {p.relative_to(checkout) for p in new}
    assert not list(ROOT.joinpath("bench").rglob("toylm*"))
