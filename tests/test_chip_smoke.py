"""``chip_smoke.py`` on the CPU: its phases at tiny width, its refusal to
run without a TPU, and the guards it leans on (client-mesh size, compile
cache placement)."""
import importlib.util
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from repro.launch.mesh import make_client_mesh
from repro.models.cnn import CNNConfig

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
SCRIPT = os.path.join(ROOT, "chip_smoke.py")

# four freeze blocks like ResNet-18, at toy width and resolution
TINY = CNNConfig("tiny_resnet", "resnet", stage_sizes=(1, 1, 1, 1),
                 stage_channels=(4, 8, 8, 8), num_classes=4)
TINY_SIZES = dict(samples=96, image_size=8)


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["PYTHONPATH"] = os.path.join(ROOT, "src") + os.pathsep + env.get(
        "PYTHONPATH", "")
    env.update(extra)
    return env


def test_phase_stages_tiny(smoke):
    """All four stages walked, finite, cached logits == recompute."""
    fails = smoke.phase_stages(TINY, clients=6, per_round=3, batch_size=16,
                               rounds_per_stage=1, **TINY_SIZES)
    assert fails == []


def test_phase_pallas_fold_tiny(smoke):
    fails = smoke.phase_pallas_fold(TINY, clients=4, compress_ratio=0.1,
                                    **TINY_SIZES)
    assert fails == []


def test_phase_sharded_tiny_on_four_host_devices():
    """The ``--chips 4`` phase on four forced host devices (the flag has to
    be set before jax initializes, hence the subprocess)."""
    code = textwrap.dedent(f"""
        import importlib.util, json, sys
        from repro.models.cnn import CNNConfig
        spec = importlib.util.spec_from_file_location("s", {SCRIPT!r})
        s = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(s)
        cfg = CNNConfig("tiny_resnet", "resnet", stage_sizes=(1, 1, 1, 1),
                        stage_channels=(4, 8, 8, 8), num_classes=4)
        print("JSON:" + json.dumps(s.phase_sharded(
            cfg, chips=4, clients=8, samples=96, image_size=8)))
    """)
    proc = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=600, cwd=ROOT,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [l for l in proc.stdout.splitlines() if l.startswith("JSON:")]
    assert line and json.loads(line[-1][5:]) == [], proc.stdout[-2000:]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_script_refuses_without_tpu(where, tmp_path):
    """No TPU (or no repo beside the script): non-zero exit, no result."""
    script = SCRIPT
    if where == "alone":
        script = str(tmp_path / "chip_smoke.py")
        shutil.copy(SCRIPT, script)
    env = _env()
    env.pop("PYTHONPATH")
    proc = subprocess.run([sys.executable, script], capture_output=True,
                          text=True, timeout=300, cwd=os.path.dirname(script),
                          env=env)
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    assert "round" not in proc.stdout


def test_client_mesh_raises_beyond_visible_devices():
    n = len(jax.devices())
    assert make_client_mesh(n).shape["clients"] == n
    with pytest.raises(ValueError, match="visible"):
        make_client_mesh(n + 1)


def test_train_mesh_clients_raises_beyond_visible_devices():
    from repro.launch.train import train
    with pytest.raises(ValueError, match="visible"):
        train("llama3-8b", steps=1, batch=1, seq=8,
              mesh_clients=len(jax.devices()) + 1)


@pytest.mark.parametrize("env_dir", [True, False])
def test_compile_cache_placement(env_dir, tmp_path):
    """``JAX_COMPILATION_CACHE_DIR`` set: entries land there and the
    repo's own directory is never configured. Unset: the repo's fixed
    ``.jax_cache`` (redirected here so the test leaves the checkout
    alone)."""
    env_cache, repo_cache = tmp_path / "env", tmp_path / "repo"
    code = textwrap.dedent(f"""
        import jax, jax.numpy as jnp
        from repro.launch import cache
        assert cache.REPO_CACHE_DIR == cache.Path({ROOT!r}) / ".jax_cache"
        cache.REPO_CACHE_DIR = cache.Path({str(repo_cache)!r})
        print("DIR:" + cache.use_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        jax.jit(lambda x: jnp.sin(x) * 3)(jnp.ones(8)).block_until_ready()
    """)
    extra = {"JAX_COMPILATION_CACHE_DIR": str(env_cache)} if env_dir else {}
    env = _env(**extra)
    if not env_dir:
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
    proc = subprocess.run([sys.executable, "-c", code], capture_output=True,
                          text=True, timeout=300, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    want, other = ((env_cache, repo_cache) if env_dir
                   else (repo_cache, env_cache))
    assert f"DIR:{want}" in proc.stdout
    assert want.is_dir() and any(want.iterdir())
    assert not other.exists()
