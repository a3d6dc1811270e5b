"""A configuration, a traffic mix, a cell and a per-layer metric are added
as new files and entries only, and the harness runs them: no file the
benchmark already has is edited."""
import hashlib
import json

from conftest import run_cli


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
