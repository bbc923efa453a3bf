"""chip_smoke.py refuses to report success off the chip or off the fused
path (its chip-side checks run on a TPU; these are the CPU-side ones)."""
import importlib.util
import os
import pathlib
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _load_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_smoke_fails_without_a_tpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_rejects_a_plan_off_the_fused_path(monkeypatch):
    """A cost model that falls back to the u64 reference ("mo") fails the
    smoke before any result line."""
    import repro  # noqa: F401
    from repro.core import compile as compile_mod
    from repro.core.params import toy_params

    smoke = _load_smoke()
    monkeypatch.setattr(smoke, "SHAPE", (4, 4, 4))
    monkeypatch.setattr(compile_mod, "select_schedule",
                        lambda *a, **k: "mo")
    with pytest.raises(SystemExit, match="left the fused Pallas path"):
        smoke.one_chip(toy_params(logN=6, L=4, k=2, beta=5, scale_bits=28))
