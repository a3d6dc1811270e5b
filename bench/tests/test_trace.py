"""The reduction from trace to per-layer metrics: on hand-made reduced
traces with known answers, on a small trace recorded on the CPU and on one
round recorded on a TPU v5e."""
import pytest

from conftest import ROOT
from bench import harness, trace

MS = 1_000_000  # ns


def _trace(devices, host):
    return trace.Trace({"devices": devices, "host": host})


def _window(lo=0, hi=100 * MS, rounds=((0, 40 * MS), (50 * MS, 90 * MS))):
    return ([[lo, hi - lo, "bench.window"]]
            + [[a, b - a, "bench.round"] for a, b in rounds])


def _read(name, tr, **ctx):
    """``name``'s reader on ``tr``; the untraced window defaults to the
    traced one of ``_window``: two rounds in 0.1 s."""
    mod = harness.load_module(harness.BENCH / "metrics" / f"{name}.py",
                              f"bench_metric_{name}")
    return mod.read({"trace": tr, "rounds": 2, "window_s": 0.1, **ctx})


def test_busy_is_the_union_of_overlapping_ops():
    ops = [[10 * MS, 10 * MS, "a"], [15 * MS, 10 * MS, "b"],   # 10..25
           [60 * MS, 5 * MS, "c"], [60 * MS, 2 * MS, "d"]]     # 60..65
    tr = _trace({"/device:TPU:0": ops}, _window())
    assert tr.busy("/device:TPU:0") == [(10 * MS, 25 * MS),
                                        (60 * MS, 65 * MS)]
    assert tr.busy_ns() == 20 * MS
    assert _read("device_idle_share", tr) == pytest.approx(80.0)


def test_ops_outside_the_window_are_clipped():
    ops = [[-5 * MS, 10 * MS, "early"], [95 * MS, 10 * MS, "late"]]
    tr = _trace({"/device:TPU:0": ops}, _window())
    assert tr.busy_ns() == 10 * MS
    assert tr.top_ops() == [("early", 0.005), ("late", 0.005)]


def test_busy_and_idle_average_over_chips():
    tr = _trace({"/device:TPU:0": [[0, 60 * MS, "x"]],
                 "/device:TPU:1": [[0, 20 * MS, "x"]]}, _window())
    assert tr.busy_ns() == 40 * MS
    assert _read("device_idle_share", tr) == pytest.approx(60.0)


def test_idle_share_is_the_untraced_windows():
    """20 ms of device time a traced round; the untraced window ran 10
    rounds in 0.5 s, so its device was busy 0.2 s of it."""
    tr = _trace({"/device:TPU:0": [[0, 40 * MS, "x"]]}, _window())
    assert _read("device_idle_share", tr, rounds=10,
                 window_s=0.5) == pytest.approx(60.0)


def test_per_round_normalisation():
    """Two rounds in the traced window (a third one past its end is not
    one): device time is divided by two. Counted work is the untraced
    window's rounds over its seconds."""
    rounds = ((0, 40 * MS), (50 * MS, 90 * MS), (95 * MS, 120 * MS))
    tr = _trace({"/device:TPU:0": [[0, 30 * MS, "x"]]},
                _window(rounds=rounds))
    assert len(tr.rounds()) == 2
    assert _read("round_device_ms", tr) == pytest.approx(15.0)
    mfu = _read("round_mfu", tr, chips=1, flops_per_round=1e9,
                peak={"bf16_flops": 1e12}, rounds=6, window_s=0.3)
    # 6 rounds x 1 GFLOP in 0.3 s on a 1 TFLOP/s chip: 2%
    assert mfu == pytest.approx(2.0)


def test_all_reduce_pick_up_and_exposed_part():
    """All-reduce ops by their XLA names; the part with no other op beside
    it is the exposed part."""
    ops = [[0, 20 * MS, "fusion.3"],
           [15 * MS, 10 * MS, "all-reduce.7"],        # 5 ms exposed
           [60 * MS, 4 * MS, "all-reduce-start.1"],   # 4 ms exposed
           [70 * MS, 1 * MS, "convolution.2"]]
    tr = _trace({"/device:TPU:0": ops, "/device:TPU:1": ops}, _window())
    assert tr.collective_ns() == (14 * MS, 9 * MS)


def test_no_collective_reads_nothing():
    tr = _trace({"/device:TPU:0": [[0, 20 * MS, "fusion"]]}, _window())
    assert tr.collective_ns() == (0.0, 0.0)


def test_gap_attribution():
    """Idle gaps, longest first, by what the host did for most of each:
    a compile event, a round's span, or the time between rounds."""
    ops = [[5 * MS, 10 * MS, "x"],     # idle 0..5 in round 1
           [38 * MS, 2 * MS, "x"],     # idle 15..38 in round 1
           [55 * MS, 10 * MS, "x"]]    # idle 40..55 mostly between rounds
    host = _window() + [[70 * MS, 25 * MS, "backend_compile"]]
    tr = _trace({"/device:TPU:0": ops}, host)
    assert [(n, pytest.approx(s)) for n, s in tr.gaps()] == [
        ("compile", 0.035), ("run_round", 0.023),
        ("between_rounds", 0.015), ("run_round", 0.005)]


def test_compiles_in_window_is_the_counter():
    assert _read("compiles_in_window", None, compiles=3) == 3.0


def test_window_span_is_required():
    with pytest.raises(ValueError, match="bench.window"):
        _trace({}, [[0, 1, "bench.round"]])


def test_recorded_cpu_trace():
    """Three rounds of a jitted matmul, recorded with ``jax.profiler`` on
    the CPU: 2 ms of host work opens each round and 3 ms follow it."""
    reduced = trace.reduce_xspace(
        str(ROOT / "bench/tests/data/cpu_rounds.xplane.pb"))
    assert reduced["devices"], "no device ops found"
    names = [n for _, _, n in reduced["host"]]
    assert names.count("bench.window") == 1
    assert names.count("bench.round") == 3
    tr = trace.Trace(reduced)
    assert len(tr.rounds()) == 3
    assert 0 < tr.busy_ns() < tr.window_ns
    assert 0 < _read("device_idle_share", tr) < 100
    labels = {n for n, _ in tr.gaps()}
    assert {"run_round", "between_rounds"} <= labels


def test_short_names_and_self_time():
    assert trace.short_name(
        "%fusion.3 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop") == \
        "fusion.3"
    assert trace.short_name("all-reduce.1") == "all-reduce.1"
    # a while holding two ops, one of them holding a third
    ops = trace.with_self_time([[10, 5, "b"], [0, 100, "while"],
                                [40, 30, "c"], [45, 10, "d"]])
    assert ops == [[0, 100, "while", 65], [10, 5, "b", 5],
                   [40, 30, "c", 20], [45, 10, "d", 10]]


def test_recorded_chip_round():
    """One stage-0 ResNet-18 round (10 clients x 16 steps) traced on a TPU
    v5e, trimmed to its own span: 7241 ops, 265.5 ms busy. The scan's
    ``while`` is not an op of its own in the ranking."""
    import gzip
    import json

    reduced = json.loads(gzip.decompress(
        (ROOT / "bench/tests/data/tpu_v5e_resnet18_s0_round.json.gz")
        .read_bytes()))
    tr = trace.Trace(reduced)
    assert len(tr.rounds()) == 1
    assert len(reduced["devices"]["/device:TPU:0"]) == 7241
    assert tr.busy_ns() * 1e-6 == pytest.approx(265.49, abs=0.01)
    assert _read("round_device_ms", tr) == pytest.approx(265.49, abs=0.01)
    top = tr.top_ops(10)
    assert len(top) == 10 and not any(n.startswith("while") for n, _ in top)
    assert sum(s for _, s in tr.top_ops(10_000)) == pytest.approx(
        tr.busy_ns() * 1e-9, rel=1e-3)
    assert tr.collective_ns() == (0.0, 0.0)
