"""Every cell of ``BENCHMARK.json`` end to end on the CPU at a tiny width
(the four-chip cell on four forced host devices), and the refusals."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from conftest import CELLS, ROOT, SPEC, run_cli

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def _result(out):
    line = json.loads(out[-1])
    assert RESULT_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    return line


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell,chips", CELLS)
def test_cell_runs_and_checks(checkout, cell, chips, trace):
    rc, out, err = run_cli(checkout, ["--workload", cell, "--seed",
                                      str(2 ** 33 + 5), "--seconds", "1",
                                      "--trace", str(trace)], devices=chips)
    assert rc == 0, "\n".join(err[-40:])
    line = _result(out)
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    dev = line["device"]
    assert dev["platform"] == "cpu" and dev["count"] == chips
    listed = [m for m in SPEC["end_to_end" if not trace else "per_layer"]
              if cell in m.get("workloads", [cell])]
    assert set(line["metrics"]) == {m["name"] for m in listed}
    for m in listed:
        assert line["metrics"][m["name"]]["unit"] == m["unit"]
    if trace:
        assert dev["busy_s"] > 0 and dev["window_s"] > 0
        assert line["metrics"]["compiles_in_window"]["value"] == 0
        assert 0 < line["metrics"]["device_idle_share"]["value"] < 100
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert 0 < len(line["breakdown"]["device_ops"]) <= 10
    # the numbers compared, each beside its limit, close stderr
    checks = [l for l in err if l.startswith("check ")]
    assert len(checks) == len(line["checks"])
    assert err[-len(checks):] == checks


def test_refuses_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0][0],
         "--seed", "1", "--seconds", "1"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout
    assert "TPU" in proc.stderr


def test_refuses_in_a_checkout_without_the_program(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0][0],
         "--seed", "1", "--seconds", "1"], cwd=tmp_path, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert "{" not in proc.stdout


def test_unknown_device_kind_is_an_error():
    from bench import harness

    assert harness.peak_of("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        harness.peak_of("TPU v99")
