"""CLI launcher smoke tests (subprocess: real entrypoints end to end)."""
import os
import subprocess
import sys

import pytest

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(args, cache_dir, timeout=600):
    # the launchers keep a persistent compile cache; point it at the test's
    # own directory instead of the checkout
    env = dict(os.environ, PYTHONPATH=SRC, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(cache_dir))
    out = subprocess.run([sys.executable, "-m"] + args, env=env,
                         capture_output=True, text=True, timeout=timeout)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-2000:])
    return out.stdout


@pytest.mark.slow
def test_train_launcher_smoke(tmp_path):
    cache = tmp_path / "jax_cache"
    out = _run(["repro.launch.train", "--arch", "internlm2-1.8b", "--smoke",
                "--steps", "4", "--global-batch", "2", "--seq", "32",
                "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"], cache)
    assert "[train] finished at step 4" in out
    assert any(d.startswith("step_") for d in os.listdir(tmp_path))
    # elastic resume from the checkpoint
    out2 = _run(["repro.launch.train", "--arch", "internlm2-1.8b", "--smoke",
                 "--steps", "6", "--global-batch", "2", "--seq", "32",
                 "--ckpt-dir", str(tmp_path), "--ckpt-every", "2"], cache)
    assert "elastic resume from step 4" in out2


@pytest.mark.slow
def test_serve_launcher_smoke(tmp_path):
    out = _run(["repro.launch.serve", "--arch", "qwen2-7b", "--smoke",
                "--requests", "2", "--max-new", "4"], tmp_path)
    assert "[serve] 2 requests" in out
