"""The engine's Mult·relin, key switch, Rescale and Add each run as one
compiled program per (params, datapath, level): bit-identical to the same
call run op by op under ``jax.disable_jit()``, traced once per level
whatever the scales, and fed the keys as arguments, so a new keygen needs
no retrace."""
import dataclasses

import numpy as np
import pytest
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.configs.fame_sets import FAME_VERIFY_SETS
from repro.core import ckks
from repro.core.ckks import CkksEngine
from repro.core.compile import HEContext, compile_hemm
from repro.core.hemm import decrypt_matrix, encrypt_matrix, plan_hemm
from repro.core.params import toy_params

CKKS_TEST_SET = toy_params(logN=7, L=4, k=3, beta=2, scale_bits=26)
SETS = {**FAME_VERIFY_SETS, "test-ckks": CKKS_TEST_SET}
PROGRAMS = (ckks._mult_program, ckks._key_switch_program,
            ckks._rescale_program, ckks._add_program)


def _assert_same(got, want):
    np.testing.assert_array_equal(np.asarray(got.c0), np.asarray(want.c0))
    np.testing.assert_array_equal(np.asarray(got.c1), np.asarray(want.c1))
    assert (got.level, got.scale) == (want.level, want.scale)


def _ops(eng, keys, ct1, ct2):
    """Mult, Rescale, Add and a Rot (whose key switch is its own program)."""
    prod = eng.mult(ct1, ct2, keys)
    res = eng.rescale(prod)
    return dict(mult=prod, rescale=res, add=eng.add(res, res),
                rotate=eng.rotate(ct1, 3, keys))


@pytest.fixture(scope="module", params=sorted(SETS))
def runs(request):
    """Each op compiled, and the same calls op by op (the reference)."""
    params = SETS[request.param]
    eng = CkksEngine(params)
    rng = np.random.default_rng(7)
    keys = eng.keygen(rng, rot_steps=[3])
    ct1, ct2 = (eng.encrypt(eng.encode(rng.normal(size=params.slots)), keys,
                            rng) for _ in range(2))
    got = _ops(eng, keys, ct1, ct2)
    with jax.disable_jit():
        want = _ops(eng, keys, ct1, ct2)
    return got, want


@pytest.mark.parametrize("op", ["mult", "rescale", "add", "rotate"])
def test_compiled_op_equals_its_op_by_op_run(runs, op):
    got, want = runs
    _assert_same(got[op], want[op])


def test_pallas_datapath_equals_the_xla_one():
    """The programs are keyed on the datapath too; both give one answer."""
    params = FAME_VERIFY_SETS["fame-s-rt"]
    xla, pal = CkksEngine(params), CkksEngine(params, datapath="pallas")
    rng = np.random.default_rng(8)
    keys = xla.keygen(rng, rot_steps=[3])
    ct1, ct2 = (xla.encrypt(xla.encode(rng.normal(size=params.slots)), keys,
                            rng) for _ in range(2))
    want, got = _ops(xla, keys, ct1, ct2), _ops(pal, keys, ct1, ct2)
    for op in want:
        _assert_same(got[op], want[op])


@pytest.mark.parametrize("which", ["mod_down", "mod_down_rescale",
                                   "rescale"])
def test_stacked_polys_drop_limbs_as_one_at_a_time(which):
    """ModDown and Rescale run one iNTT and one NTT over the rows of every
    stacked polynomial; each comes out as it would alone."""
    eng = CkksEngine(FAME_VERIFY_SETS["fame-s-rt"])
    p, ell = eng.params, 3
    rng = np.random.default_rng(12)
    rows = ell + 1 + p.k if which.startswith("mod_down") else ell + 1
    qs = np.array([eng.ctx.moduli_host[i] for i in
                   list(range(ell + 1)) + list(range(p.num_main, p.num_total))
                   ][:rows], dtype=np.uint64)[:, None]
    x = np.stack([rng.integers(0, qs, size=(rows, p.N)) for _ in range(2)]
                 ).astype(np.uint32)
    f = {"mod_down": lambda v: eng._mod_down_eval(v, ell),
         "mod_down_rescale": lambda v: eng._mod_down_eval(
             v, ell, drop_last=True, datapath="xla"),
         "rescale": lambda v: eng._rescale_poly(v, ell)}[which]
    both = np.asarray(f(jnp.asarray(x)))
    for i in range(2):
        np.testing.assert_array_equal(both[i], np.asarray(f(jnp.asarray(x[i]))))


def _hemm_setup(params, seed, m=4, l=3, n=5):
    rng = np.random.default_rng(seed)
    ctx = HEContext(CkksEngine(params))
    plan = plan_hemm(ctx.eng, m, l, n)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    A = rng.uniform(-1, 1, (m, l))
    B = rng.uniform(-1, 1, (l, n))
    return ctx, plan, rng, A, B


def test_hemm_program_equals_its_op_by_op_run():
    ctx, plan, rng, A, B = _hemm_setup(FAME_VERIFY_SETS["fame-s-rt"], 9)
    prog = compile_hemm(ctx, plan)
    ctA = encrypt_matrix(ctx.eng, ctx.keys, A, rng)
    ctB = encrypt_matrix(ctx.eng, ctx.keys, B, rng)
    got = prog(ctA, ctB)
    with jax.disable_jit():
        want = prog(ctA, ctB)
    _assert_same(got, want)


def test_l_products_trace_each_program_once_and_a_new_keygen_retraces_none():
    # a name of its own: no other test has traced programs for these params
    params = dataclasses.replace(FAME_VERIFY_SETS["fame-s-rt"],
                                 name="compile-once")
    ctx, plan, rng, A, B = _hemm_setup(params, 10)
    eng = ctx.eng
    before = [f._cache_size() for f in PROGRAMS]

    # l products at one level, each pair at a scale of its own
    ell = params.L
    acc = None
    for k in range(plan.l):
        a, b = (eng.encrypt(eng.encode(rng.normal(size=params.slots),
                                       scale=params.scale * (1 + k / 8)),
                            ctx.keys, rng) for _ in range(2))
        prod = eng.rescale(eng.mult(a, b, ctx.keys))
        acc = prod if acc is None else eng.add(acc, prod)
    assert acc.level == ell - 1
    traced = [f._cache_size() - n for f, n in zip(PROGRAMS, before)]
    assert traced == [1, 0, 1, 1]

    # a product through the compiled HE MM, then new keys: programs take the
    # keys as arguments, so the product under the new keys decrypts right
    # and traces nothing new
    m, n = A.shape[0], B.shape[1]
    for rekey in (False, True):
        if rekey:
            traced_once = [f._cache_size() for f in PROGRAMS]
            old_s = ctx.keys.s_eval
            ctx.keygen(rng, rot_steps=plan.rot_steps)
            assert not np.array_equal(np.asarray(old_s),
                                      np.asarray(ctx.keys.s_eval))
        prog = compile_hemm(ctx, plan)
        ctA = encrypt_matrix(eng, ctx.keys, A, rng)
        ctB = encrypt_matrix(eng, ctx.keys, B, rng)
        C = decrypt_matrix(eng, ctx.keys, prog(ctA, ctB), m, n)
        np.testing.assert_allclose(C, A @ B, atol=2e-2)
    assert [f._cache_size() for f in PROGRAMS] == traced_once
