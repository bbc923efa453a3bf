"""On-disk cache of ``plan_hemm``'s result.

A plan (the transformation matrices' diagonals, encoded over the full prime
basis) is a pure function of the parameters, the shape and the program's
source. Building it is host numpy work of a minute or more at Set-A width,
paid by every run; the cache pays it once per checkout. The key covers the
parameters, the shape and a hash of every ``src/repro/**/*.py``, so a change
to the program can never read a stale plan.
"""
from __future__ import annotations

import hashlib
import json
import os
import pathlib

import numpy as np

from bench.cells import HERE, ROOT

CACHE = HERE / ".cache" / "plans"


def source_hash(src: pathlib.Path = ROOT / "src" / "repro") -> str:
    h = hashlib.sha256()
    for path in sorted(src.rglob("*.py")):
        h.update(path.relative_to(src).as_posix().encode())
        h.update(b"\0")
        h.update(path.read_bytes())
        h.update(b"\0")
    return h.hexdigest()


def key(params: dict, shape, src: pathlib.Path = ROOT / "src" / "repro") -> str:
    blob = json.dumps({"params": params, "shape": list(shape),
                       "src": source_hash(src)}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()[:32]


def _flatten(plan) -> dict:
    sets = {"sigma": [plan.ds_sigma], "tau": [plan.ds_tau],
            "eps": list(plan.ds_eps), "omega": list(plan.ds_omega)}
    out = {"mln": np.array([plan.m, plan.l, plan.n]),
           "rot_steps": np.array(plan.rot_steps, np.int64)}
    for group, dss in sets.items():
        out[f"{group}.count"] = np.array(len(dss))
        for i, ds in enumerate(dss):
            out[f"{group}.{i}.zs"] = np.array(ds.zs, np.int64)
            out[f"{group}.{i}.pt"] = np.asarray(ds.pt)
            out[f"{group}.{i}.scale"] = np.array(ds.scale, np.float64)
            out[f"{group}.{i}.shape"] = np.array(ds.shape, np.int64)
    return out


def _unflatten(z):
    import jax.numpy as jnp
    from repro.core.hemm import HeMMPlan
    from repro.core.hlt import DiagSet

    def sets(group):
        return [DiagSet(zs=tuple(int(v) for v in z[f"{group}.{i}.zs"]),
                        pt=jnp.asarray(z[f"{group}.{i}.pt"]),
                        scale=float(z[f"{group}.{i}.scale"]),
                        shape=tuple(int(v) for v in z[f"{group}.{i}.shape"]))
                for i in range(int(z[f"{group}.count"]))]
    m, l, n = (int(v) for v in z["mln"])
    return HeMMPlan(m, l, n, sets("sigma")[0], sets("tau")[0], sets("eps"),
                    sets("omega"), tuple(int(v) for v in z["rot_steps"]))


def load_or_build(eng, params: dict, shape, cache=None):
    """(plan, hit): the cached plan for ``params``/``shape``, or a new one
    from ``plan_hemm`` that is then written to ``cache`` (default
    ``bench/.cache/plans``)."""
    from repro.core.hemm import plan_hemm
    cache = CACHE if cache is None else pathlib.Path(cache)
    path = cache / f"{key(params, shape)}.npz"
    if path.is_file():
        with np.load(path) as z:
            return _unflatten(z), True
    plan = plan_hemm(eng, *shape)
    cache.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npz")
    np.savez(tmp, **_flatten(plan))
    os.replace(tmp, path)
    return plan, False
