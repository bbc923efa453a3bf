"""bench/plancache.py: the key follows the program's source, the
parameters and the shape, and a cached plan is the plan plan_hemm builds."""
import numpy as np
import pytest

from bench import plancache

PARAMS = {"name": "toy", "logN": 6, "L": 4, "k": 3, "beta": 2,
          "scale_bits": 26, "q0_bits": 29, "sp_bits": 30}


def _tree(tmp_path, body):
    src = tmp_path / "repro"
    (src / "core").mkdir(parents=True)
    (src / "__init__.py").write_text("")
    (src / "core" / "hemm.py").write_text(body)
    return src


def test_key_changes_with_the_source(tmp_path):
    src = _tree(tmp_path, "x = 1\n")
    before = plancache.key(PARAMS, (4, 4, 4), src)
    assert plancache.key(PARAMS, (4, 4, 4), src) == before
    (src / "core" / "hemm.py").write_text("x = 2\n")
    assert plancache.key(PARAMS, (4, 4, 4), src) != before
    (src / "core" / "hemm.py").write_text("x = 1\n")
    (src / "core" / "new.py").write_text("")
    assert plancache.key(PARAMS, (4, 4, 4), src) != before


def test_key_changes_with_params_and_shape(tmp_path):
    src = _tree(tmp_path, "")
    base = plancache.key(PARAMS, (4, 4, 4), src)
    assert plancache.key(dict(PARAMS, beta=5), (4, 4, 4), src) != base
    assert plancache.key(PARAMS, (4, 4, 2), src) != base


def test_the_real_source_hash_covers_the_program():
    h = plancache.source_hash()
    assert len(h) == 64 and h == plancache.source_hash()


@pytest.fixture(scope="module")
def eng():
    import repro  # noqa: F401
    from repro.core.ckks import CkksEngine
    from repro.core.params import HEParams
    return CkksEngine(HEParams(**PARAMS))


def test_cached_plan_equals_a_fresh_one(eng, tmp_path):
    from repro.core.hemm import plan_hemm
    built, hit = plancache.load_or_build(eng, PARAMS, (4, 4, 2), tmp_path)
    assert not hit and len(list(tmp_path.glob("*.npz"))) == 1
    cached, hit = plancache.load_or_build(eng, PARAMS, (4, 4, 2), tmp_path)
    assert hit
    fresh = plan_hemm(eng, 4, 4, 2)
    assert (cached.m, cached.l, cached.n) == (4, 4, 2)
    assert cached.rot_steps == fresh.rot_steps
    pairs = ([(cached.ds_sigma, fresh.ds_sigma), (cached.ds_tau, fresh.ds_tau)]
             + list(zip(cached.ds_eps, fresh.ds_eps, strict=True))
             + list(zip(cached.ds_omega, fresh.ds_omega, strict=True)))
    for a, b in pairs:
        assert a.zs == b.zs and a.scale == b.scale and a.shape == b.shape
        assert np.array_equal(np.asarray(a.pt), np.asarray(b.pt))
        assert a.pt.dtype == b.pt.dtype
