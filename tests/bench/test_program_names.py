"""The program names ``bench/programs.json`` matches to the HLT steps exist.

``hlt_device_s`` sums the device programs whose names match its patterns
and ``tail_device_s`` takes the rest, so a renamed jitted function would
move its time into the tail without a sound. This lowers the main path's
Step-2 launch and the batched hoist at toy size and asserts each pattern
matches one of their program names.
"""
import json
import re

import numpy as np
import pytest
import jax

import repro  # noqa: F401
from bench.cells import ROOT
from repro.core.ckks import CkksEngine
from repro.core.compile import HEContext, compile_hemm
from repro.core.hemm import encrypt_matrix, plan_hemm
from repro.core.hlt import hoist_batched
from repro.core.params import toy_params

TOY = toy_params(logN=6, L=4, k=3, beta=2, scale_bits=26)


def _eager_launch_names(fn) -> list:
    """The names the programs of ``fn``'s eager dispatches run under: one
    per top-level equation, ``jit_<function>`` for a jitted function and
    ``jit_<primitive>`` otherwise."""
    eqns = jax.make_jaxpr(fn)().eqns
    return ["jit_" + (e.params["name"] if e.primitive.name in ("jit", "pjit")
                      else e.primitive.name) for e in eqns]


@pytest.fixture(scope="module")
def names():
    rng = np.random.default_rng(5)
    ctx = HEContext(CkksEngine(TOY))
    plan = plan_hemm(ctx.eng, 4, 3, 5)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    prog = compile_hemm(ctx, plan)
    assert (prog.plan.step1.schedule, prog.plan.step1.datapath) == \
        ("pallas", "pallas")
    ctA = encrypt_matrix(ctx.eng, ctx.keys, rng.uniform(-1, 1, (4, 3)), rng)
    ctB = encrypt_matrix(ctx.eng, ctx.keys, rng.uniform(-1, 1, (3, 5)), rng)
    step2 = re.match(r"HloModule (\S+?),", prog.hlo(ctA, ctB)).group(1)
    hoist = _eager_launch_names(lambda: [
        (h.digits, h.c0_ext, h.c1_ext)
        for h in hoist_batched(ctx.eng, [ctA, ctB], datapath="pallas")])
    return {"step2": step2, "hoist": hoist}


def test_step2_launch_is_an_hlt_program(names):
    pats = json.loads((ROOT / "bench" / "programs.json").read_text())
    assert any(re.search(p, names["step2"])
               for p in pats["hlt"]["patterns"]), names["step2"]


@pytest.mark.parametrize("pattern", json.loads(
    (ROOT / "bench" / "programs.json").read_text())["hlt"]["patterns"])
def test_each_pattern_names_a_program(names, pattern):
    found = [names["step2"]] + names["hoist"]
    assert any(re.search(pattern, n) for n in found), (pattern, found)
