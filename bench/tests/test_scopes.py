"""Device time under the program's ``jax.named_scope``s
(``bench/scopes.py``) and its readers: on hand-made ops, on traces
recorded here on the CPU, on a small program and two VGG-16_bn rounds
recorded on a TPU v5e, and the benchmark's earlier readings, which the
scopes leave as they were."""
import gzip
import json

import pytest

from conftest import ROOT
from bench import harness, scopes, trace

MS = 1_000_000  # ns
DATA = ROOT / "bench/tests/data"


def _window(rounds=((0, 40 * MS), (50 * MS, 90 * MS))):
    return ([[0, 100 * MS, "bench.window"]]
            + [[a, b - a, "bench.round"] for a, b in rounds])


def _read(name, tr, **ctx):
    mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                              f"bench_metric_{name}")
    return mod.read({"trace": tr, "rounds": 2, "window_s": 0.1, **ctx})


def test_busy_is_the_union_of_the_scopes_ops():
    """An op counts for a scope when the scope is a whole part of its
    path, under whatever transformation wraps it; clipped to the window,
    averaged over the devices."""
    ops = [(0, 10 * MS, "jit(f)/vmap(local_train)/while"),
           (5 * MS, 15 * MS, "jit(f)/transpose(jvp(local_train))"),
           (20 * MS, 22 * MS, "jit(f)/local_train/prefix"),
           (30 * MS, 33 * MS, "jit(f)/fold"),
           (40 * MS, 44 * MS, "jit(f)/local_training"),
           (95 * MS, 110 * MS, "jit(f)/fold"),
           (50 * MS, 51 * MS, "")]
    tr = trace.Trace({"devices": {}, "host": _window()})
    one = {"/device:TPU:0": ops}
    assert scopes.busy_ns(tr, one, "local_train") == 17 * MS
    assert scopes.busy_ns(tr, one, "prefix") == 2 * MS
    assert scopes.busy_ns(tr, one, "fold") == 8 * MS
    assert scopes.busy_ns(tr, one, "absent") == 0
    two = {"/device:TPU:0": ops, "/device:TPU:1": []}
    assert scopes.busy_ns(tr, two, "local_train") == 8.5 * MS
    assert scopes.busy_ns(tr, {}, "local_train") == 0


def _record(tmp_path, *, scoped=True):
    """Two rounds of a small jitted step traced here on the CPU, with the
    benchmark's spans; the step's ops under ``local_train`` and ``fold``
    where ``scoped``."""
    import contextlib

    import jax
    import jax.numpy as jnp

    def scope(name):
        return jax.named_scope(name) if scoped else contextlib.nullcontext()

    @jax.jit
    def step(x):
        with scope("local_train"):
            y = jnp.tanh(x @ x)
        with scope("fold"):
            return y.sum(0)

    x = jnp.ones((128, 128))
    step(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(2):
            with jax.profiler.TraceAnnotation("bench.round"):
                step(x).block_until_ready()
    jax.profiler.stop_trace()
    return trace.find_xspace(str(tmp_path))


def test_readers_on_a_cpu_trace(tmp_path, monkeypatch):
    """The readers find the profile where the run writes it and read each
    scope's device time per traced round; a scope the program lacks reads
    nothing."""
    path = _record(tmp_path)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    tr = trace.Trace(trace.reduce_xspace(path))
    ops = scopes.scoped_ops(path)
    assert set(ops) == set(tr.devices) == {"/host:CPU"}
    assert any("local_train" in p for _, _, p in ops["/host:CPU"])
    local = _read("local_train_device_ms", tr)
    fold = _read("fold_device_ms", tr)
    assert local > 0 and fold > 0
    assert (local + fold) * 2 <= tr.busy_ns() * 1e-6 * (1 + 1e-9)
    assert _read("prefix_device_ms", tr) is None


def test_readers_read_nothing_without_scopes(tmp_path, monkeypatch):
    """A program without scopes (the parent's), and a run without a
    profile: every new reader returns None."""
    names = ("local_train_device_ms", "fold_device_ms", "prefix_device_ms")
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "none")
    tr = trace.Trace({"devices": {"/device:TPU:0": [[0, 20 * MS, "f.1"]]},
                      "host": _window()})
    for name in names:
        assert _read(name, tr) is None, name
    path = _record(tmp_path / "plain", scoped=False)
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path / "plain")
    tr = trace.Trace(trace.reduce_xspace(path))
    assert tr.busy_ns() > 0
    for name in names:
        assert _read(name, tr) is None, name


def test_recorded_tpu_profile_maps_ops_to_their_scopes(monkeypatch):
    """Two rounds of a small jitted step traced on a TPU v5e with the
    program's spans: each op is matched to its program by the device's
    ``XLA Modules`` line and given its op-name metadata from the program's
    HLO; the copies XLA inserts carry none."""
    path = str(DATA / "tpu_v5e_scopes.xplane.pb")
    ops = scopes.scoped_ops(path)["/device:TPU:0"]
    assert {p for _, _, p in ops} == {"", "jit(step)/local_train/prefix"}
    reduced = trace.reduce_xspace(path)
    assert [o[0] for o in reduced["devices"]["/device:TPU:0"]] == [
        a for a, _, _ in ops]
    tr = trace.Trace(reduced)
    scoped = {s: scopes.busy_ns(tr, {"t": ops}, s)
              for s in ("local_train", "prefix", "fold")}
    assert scoped["prefix"] == scoped["local_train"] > 0
    assert scoped["fold"] == 0
    assert scoped["local_train"] < tr.busy_ns()
    monkeypatch.setattr(scopes, "TRACE_DIR", DATA)
    monkeypatch.setattr(trace, "find_xspace", lambda _: path)
    assert _read("prefix_device_ms", tr) == pytest.approx(
        scoped["prefix"] / len(tr.rounds()) * 1e-6)


def test_recorded_chip_rounds_with_spans_and_scopes():
    """Two VGG-16_bn stage-3 recompute rounds (10 clients x 16 steps)
    traced on a TPU v5e, trimmed to their own spans; each op carries its
    scope path as a fifth field and the host the engine's spans. The
    scopes cover the round's device time."""
    reduced = json.loads(gzip.decompress(
        (DATA / "tpu_v5e_vgg16_s3_recompute_2rounds.json.gz").read_bytes()))
    tr = trace.Trace(reduced)
    assert len(tr.rounds()) == 2
    ops = {d: [(o[0], o[0] + o[1], o[4]) for o in v]
           for d, v in reduced["devices"].items()}
    assert len(ops["/device:TPU:0"]) == 13074
    ms = {s: scopes.busy_ns(tr, ops, s) / 2 * 1e-6
          for s in ("local_train", "fold", "prefix")}
    device = _read("round_device_ms", tr)
    assert device == pytest.approx(75.343, abs=1e-3)
    assert ms["local_train"] == pytest.approx(73.632, abs=1e-3)
    assert ms["fold"] == pytest.approx(0.408, abs=1e-3)
    assert ms["prefix"] == pytest.approx(22.689, abs=1e-3)
    assert ms["local_train"] + ms["fold"] >= 0.9 * device
    names = [n for _, _, n in reduced["host"]]
    for span in ("engine.round", "engine.gather", "engine.put",
                 "engine.dispatch", "engine.sync"):
        assert names.count(span) == 2, span
    assert not any("compile" in n for n in names)


def test_earlier_readings_unchanged_on_the_recorded_traces():
    """Every number the benchmark read before the program had spans and
    scopes reads the same on both earlier recorded traces."""
    traces = {
        "cpu": trace.reduce_xspace(str(DATA / "cpu_rounds.xplane.pb")),
        "tpu": json.loads(gzip.decompress(
            (DATA / "tpu_v5e_resnet18_s0_round.json.gz").read_bytes()))}
    want = {
        "cpu": {"round_device_ms": 0.19438133333333332,
                "device_idle_share": 99.95464435555556, "busy": 583144.0,
                "top": [("dot_general.1", 0.00045029000000000005),
                        ("wrapped_reduce-window", 6.3817e-05),
                        ("wrapped_tanh", 6.1796e-05),
                        ("wrapped_reduce", 7.241000000000001e-06)],
                "gaps": [("between_rounds", 0.005738896),
                         ("between_rounds", 0.0056736220000000006),
                         ("between_rounds", 0.0031815370000000003),
                         ("run_round", 0.00249869),
                         ("run_round", 1.63e-06), ("run_round", 1.461e-06)],
                "n_gaps": 13},
        "tpu": {"round_device_ms": 265.492754,
                "device_idle_share": 38.05169073333333, "busy": 265492754.0,
                "top": [("multiply_reduce_fusion.141", 0.023441126000000003),
                        ("multiply_reduce_fusion.143", 0.019940589),
                        ("multiply_reduce_fusion.142", 0.016439791000000002),
                        ("multiply_reduce_fusion.140", 0.016416590000000002),
                        ("fusion.311", 0.012855990000000001)],
                "gaps": ([("run_round", 0.540994041),
                          ("run_round", 0.00913953)]
                         + [("run_round", 2e-09)] * 4),
                "n_gaps": 45}}
    ctx = dict(rounds=7, window_s=3.0, compiles=2, chips=1,
               flops_per_round=1e9, peak={"bf16_flops": 1e12})
    for label, reduced in traces.items():
        tr, w = trace.Trace(reduced), want[label]
        assert _read("round_device_ms", tr, **ctx) == w["round_device_ms"]
        assert _read("device_idle_share", tr, **ctx) == \
            w["device_idle_share"]
        assert _read("round_mfu", tr, **ctx) == 0.23333333333333334
        assert _read("compiles_in_window", tr, **ctx) == 2.0
        assert tr.busy_ns() == w["busy"]
        assert tr.collective_ns() == (0.0, 0.0)
        assert tr.top_ops(5) == w["top"]
        assert tr.gaps()[:6] == w["gaps"]
        assert len(tr.gaps()) == w["n_gaps"]
