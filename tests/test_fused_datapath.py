"""The §7 fused base-change datapath (kernels/ntt.py + kernels/basechange.py):
every stage the ``datapath="pallas"`` knob moves off XLA must be BIT-exact vs
the u64 reference lowering — the knob trades lowering, not semantics.

Covers: the Pallas NTT/iNTT pass against the u64 transforms (roundtrip +
parity, both FAME verify sets), the engine-level ``CkksEngine(datapath=
"pallas")`` _ntt/_intt routing, the fused hoist (single, vmap, and the
double-buffered batched variant) and the fused merged ModDown+Rescale
against their XLA chains, and the compiled ``schedule="pallas"`` program
under ``verify="error"`` (exercising JX004 + VM001 on a fused plan) against
the ``mo`` oracle end to end.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import repro  # noqa: F401
from repro.core import hlt as hlt_mod, ntt as core_ntt
from repro.core.ckks import CkksEngine
from repro.core.params import toy_params
from repro.kernels import basechange, ntt as kntt

PARAM_SETS = [
    toy_params(logN=6, L=4, k=3, beta=2, scale_bits=26),
    toy_params(logN=7, L=5, k=2, beta=3, scale_bits=26),
]
IDS = [f"logN{p.logN}-L{p.L}-k{p.k}-b{p.beta}" for p in PARAM_SETS]


@pytest.fixture(scope="module", params=PARAM_SETS, ids=IDS)
def setup(request):
    eng = CkksEngine(request.param)           # default datapath="xla"
    rng = np.random.default_rng(11)
    keys = eng.keygen(rng)
    pt = eng.encode(rng.uniform(-1, 1, eng.params.slots))
    ct = eng.encrypt(pt, keys, rng)
    return dict(eng=eng, rng=rng, keys=keys, ct=ct)


def _rand_limbs(rng, view, n):
    qs = np.asarray(view.moduli_host, np.uint64)[:, None]
    return rng.integers(0, qs, (len(qs), n)).astype(np.uint32)


# -- the Pallas NTT/iNTT pass --------------------------------------------


def test_pallas_ntt_matches_u64_and_roundtrips(setup):
    eng, rng = setup["eng"], setup["rng"]
    view = eng.basis(np.arange(eng.params.num_total))
    x = _rand_limbs(rng, view, eng.params.N)
    xj = jnp.asarray(x)[None]
    fwd = kntt.ntt(xj, view.psi_brv_mont, view.moduli_u32, view.qneg_inv,
                   interpret=True)
    want = core_ntt.ntt(jnp.asarray(x), view.psi_brv, view.moduli)
    np.testing.assert_array_equal(np.asarray(fwd[0]), np.asarray(want))
    back = kntt.intt(fwd, view.psi_inv_brv_mont, view.n_inv_mont,
                     view.moduli_u32, view.qneg_inv, interpret=True)
    np.testing.assert_array_equal(np.asarray(back[0]), x)


def test_engine_datapath_pallas_ntt_parity(setup):
    """CkksEngine(datapath="pallas") routes _ntt/_intt through the kernel;
    the engines must agree bit for bit on the same input."""
    eng, rng = setup["eng"], setup["rng"]
    eng_p = CkksEngine(eng.params, datapath="pallas")
    view = eng.basis(np.arange(eng.params.num_total))
    x = jnp.asarray(_rand_limbs(rng, view, eng.params.N))
    np.testing.assert_array_equal(np.asarray(eng._ntt(x, view)),
                                  np.asarray(eng_p._ntt(x, view)))
    np.testing.assert_array_equal(np.asarray(eng._intt(x, view)),
                                  np.asarray(eng_p._intt(x, view)))


# -- fused hoist ----------------------------------------------------------


def _assert_hoisted_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.digits), np.asarray(b.digits))
    np.testing.assert_array_equal(np.asarray(a.c0_ext), np.asarray(b.c0_ext))
    np.testing.assert_array_equal(np.asarray(a.c1_ext), np.asarray(b.c1_ext))
    assert a.level == b.level and a.scale == b.scale


def test_hoist_fused_matches_xla(setup):
    eng, ct = setup["eng"], setup["ct"]
    _assert_hoisted_equal(hlt_mod.hoist(eng, ct, datapath="pallas"),
                          hlt_mod.hoist(eng, ct, datapath="xla"))


def test_hoist_batched_db_matches_single(setup):
    """hoist_batched on the pallas datapath runs the double-buffered kernel;
    it must equal the per-ct fused hoist AND the XLA chain."""
    eng, keys, rng = setup["eng"], setup["keys"], setup["rng"]
    cts = [eng.encrypt(eng.encode(rng.uniform(-1, 1, eng.params.slots)),
                       keys, rng) for _ in range(3)]
    batched = hlt_mod.hoist_batched(eng, cts, datapath="pallas")
    for hb, ct in zip(batched, cts):
        _assert_hoisted_equal(hb, hlt_mod.hoist(eng, ct, datapath="xla"))


def test_hoist_fused_db_kernel_matches_vmap(setup):
    """The double-buffered kernel (persistent 2-slot scratch) vs
    vmap(hoist_fused) — the DMA overlap must not change a bit."""
    eng, rng = setup["eng"], setup["rng"]
    level = eng.params.L
    t = eng.fused_hoist_tables(level)
    view = eng.main_basis(level)
    c1s = jnp.asarray(np.stack(
        [_rand_limbs(rng, view, eng.params.N) for _ in range(3)]))
    db = basechange.hoist_fused_db(c1s, t, interpret=True)
    ref = jax.vmap(lambda c: basechange.hoist_fused(c, t, interpret=True))(
        c1s)
    np.testing.assert_array_equal(np.asarray(db), np.asarray(ref))


# -- fused merged ModDown+Rescale ----------------------------------------


@pytest.mark.parametrize("drop_levels", [0, 2])
def test_moddown_fused_matches_xla(setup, drop_levels):
    eng, ct, keys = setup["eng"], setup["ct"], setup["keys"]
    rng = setup["rng"]
    ell = eng.params.L - drop_levels
    hst = hlt_mod.hoist(eng, ct, datapath="xla")
    acc = hst.c0_ext if drop_levels == 0 else jnp.asarray(_rand_limbs(
        rng, eng.basis(list(range(ell + 1)) + list(
            range(eng.params.num_main, eng.params.num_total))),
        eng.params.N))
    got = eng._mod_down_eval(acc, ell, drop_last=True, datapath="pallas")
    want = eng._mod_down_eval(acc, ell, drop_last=True, datapath="xla")
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


# -- compiled program end to end -----------------------------------------


def test_compiled_pallas_fused_verify_error_vs_mo():
    """compile under verify="error" (the JX004 + VM001 gate must admit the
    fused plan) and match the mo oracle bit for bit."""
    from repro.core.compile import HEContext, compile_hlt
    from repro.core.hemm import plan_hemm, encrypt_matrix

    rng = np.random.default_rng(5)
    ctx = HEContext(CkksEngine(PARAM_SETS[0]), verify="error",
                    datapath="pallas")
    plan = plan_hemm(ctx.eng, 4, 3, 5)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    ct = encrypt_matrix(ctx.eng, ctx.keys, rng.uniform(-1, 1, (4, 3)), rng)
    run = compile_hlt(ctx, plan.ds_sigma, level=ct.level, schedule="pallas")
    assert run.plan.datapath == "pallas"
    mo = compile_hlt(ctx, plan.ds_sigma, level=ct.level, schedule="mo")
    assert mo.plan.datapath == "xla"    # reference schedules stay XLA
    got, want = run(ct), mo(ct)
    np.testing.assert_array_equal(np.asarray(got.c0), np.asarray(want.c0))
    np.testing.assert_array_equal(np.asarray(got.c1), np.asarray(want.c1))


def test_datapath_xla_baseline_knob():
    """HEContext(datapath="xla") keeps the comparison baseline compilable:
    same schedule, XLA base-change stages, identical results."""
    from repro.core.compile import HEContext, compile_hlt
    from repro.core.hemm import plan_hemm, encrypt_matrix

    rng = np.random.default_rng(6)
    eng = CkksEngine(PARAM_SETS[0])
    ctx_p = HEContext(eng, verify="error", datapath="pallas")
    plan = plan_hemm(eng, 4, 3, 5)
    ctx_p.keygen(rng, rot_steps=plan.rot_steps)
    ctx_x = HEContext(eng, ctx_p.keys, verify="error", datapath="xla")
    ct = encrypt_matrix(eng, ctx_p.keys, rng.uniform(-1, 1, (4, 3)), rng)
    run_p = compile_hlt(ctx_p, plan.ds_sigma, level=ct.level,
                        schedule="pallas")
    run_x = compile_hlt(ctx_x, plan.ds_sigma, level=ct.level,
                        schedule="pallas")
    assert run_x.plan.datapath == "xla"
    got, want = run_p(ct), run_x(ct)
    np.testing.assert_array_equal(np.asarray(got.c0), np.asarray(want.c0))
    np.testing.assert_array_equal(np.asarray(got.c1), np.asarray(want.c1))


def test_jx004_fires_on_unfused_pallas_plan():
    """A datapath="pallas" plan whose traced hoist still contains a named
    XLA NTT must produce the JX004 diagnostic."""
    from repro.analysis import jaxpr_lint

    eng = CkksEngine(PARAM_SETS[0])
    body = hlt_mod._hoist_body(eng, eng.params.L, "xla")
    n = eng.params.N
    nq = eng.params.L + 1
    jx = jax.make_jaxpr(body)(
        jax.ShapeDtypeStruct((nq, n), np.uint32),
        jax.ShapeDtypeStruct((nq, n), np.uint32))
    assert jaxpr_lint._named_ntt_count(jx) > 0
    diags = jaxpr_lint.lint_jaxpr(jx, datapath="xla", expected_psums=0,
                                  stages="pallas")
    assert any(d.rule == "JX004" for d in diags)
    # and the fused body is clean
    jx_f = jax.make_jaxpr(hlt_mod._hoist_body(eng, eng.params.L, "pallas"))(
        jax.ShapeDtypeStruct((nq, n), np.uint32),
        jax.ShapeDtypeStruct((nq, n), np.uint32))
    assert jaxpr_lint._named_ntt_count(jx_f) == 0


def test_fused_stage_working_sets_cover_new_stages():
    from repro.core.costmodel import (fused_stage_working_sets,
                                      fused_working_set_bytes)
    p = PARAM_SETS[0]
    ws = fused_stage_working_sets(p, nbeta=p.beta, chunk=4, level=2)
    assert set(ws) == {"rot", "hoist", "moddown"}
    alpha = min(p.alpha, 3)
    assert ws["hoist"] == basechange.hoist_working_set_rows(
        p.beta, alpha, p.logN) * 4 * p.N
    assert ws["moddown"] == basechange.moddown_working_set_rows(
        p.k + 1, p.logN) * 4 * p.N
    assert fused_working_set_bytes(p, nbeta=p.beta, chunk=4,
                                   level=2) == max(ws.values())


# -- the chip's f32 BaseConv correction -----------------------------------


def _smoke_tolerance() -> float:
    import importlib.util
    import pathlib
    path = pathlib.Path(__file__).resolve().parents[1] / "chip_smoke.py"
    spec = importlib.util.spec_from_file_location("chip_smoke", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.TOLERANCE


def test_f32_basechange_tables_vs_u64_chain(setup):
    """Off the CPU the fused hoist and merged ModDown run their BaseConv
    overflow count in f32 (CkksEngine._fp_dtype). Pin that arithmetic here,
    in interpret mode, against the u64 XLA chain: the f32 count may be off
    by one where a coefficient's true count sits within ~2^-21 of an
    integer, so coefficients may differ (printed), but only rarely, and an
    HE MM through the f32 tables stays within the chip smoke's tolerance."""
    from repro.core.compile import HEContext, compile_hemm
    from repro.core.hemm import decrypt_matrix, encrypt_matrix, plan_hemm

    eng, ct, rng = setup["eng"], setup["ct"], setup["rng"]
    p = eng.params
    ell = p.L
    f32 = CkksEngine(p)
    f32._fp_dtype = lambda: np.float32          # the tables the chip builds
    assert f32.fused_hoist_tables(ell)["inv_d"].dtype == np.float32

    got = hlt_mod.hoist(f32, ct, datapath="pallas").digits
    want = hlt_mod.hoist(eng, ct, datapath="xla").digits
    acc = jnp.asarray(_rand_limbs(rng, eng.basis(
        list(range(ell + 1)) + list(range(p.num_main, p.num_total))), p.N))
    got_md = f32._mod_down_eval(acc, ell, drop_last=True, datapath="pallas")
    want_md = eng._mod_down_eval(acc, ell, drop_last=True, datapath="xla")
    diff = (int(np.sum(np.asarray(got) != np.asarray(want))),
            int(np.sum(np.asarray(got_md) != np.asarray(want_md))))
    total = (got.size, got_md.size)
    print(f"f32 vs u64: hoist {diff[0]}/{total[0]}, "
          f"moddown {diff[1]}/{total[1]} coefficients differ")
    assert diff[0] <= total[0] // 1000 and diff[1] <= total[1] // 1000

    m, l, n = 4, 3, 5
    ctx = HEContext(f32, verify="error")
    plan = plan_hemm(f32, m, l, n)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    A, B = rng.uniform(-1, 1, (m, l)), rng.uniform(-1, 1, (l, n))
    prog = compile_hemm(ctx, plan, schedule="pallas")
    C = decrypt_matrix(f32, ctx.keys, prog(
        encrypt_matrix(f32, ctx.keys, A, rng),
        encrypt_matrix(f32, ctx.keys, B, rng)), m, n)
    assert np.max(np.abs(C - A @ B)) <= _smoke_tolerance()
