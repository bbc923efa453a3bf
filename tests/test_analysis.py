"""The static verifier (repro.analysis): acceptance on every schedule,
rejection of deliberately broken programs, tracker-vs-execution exactness,
and the serving cache's verify-mode key.

The acceptance sweep uses configs/fame_sets.FAME_VERIFY_SETS — the
runtime-scaled structure-faithful twins of the paper's parameter sets.
"""
import warnings

import numpy as np
import pytest

import repro  # noqa: F401
from repro.analysis import (CtState, ScaleTracker, VerificationError,
                            VerificationWarning, trace_chain, trace_hemm,
                            verify_program)
from repro.analysis.diagnostics import RULES, Diagnostic, errors
from repro.analysis.jaxpr_lint import lint_jaxpr
from repro.configs.fame_sets import FAME_VERIFY_SETS
from repro.core.ckks import CkksEngine
from repro.core.compile import (HEContext, compile_blockmm, compile_hemm,
                                compile_hlt)
from repro.core.hemm import encrypt_matrix, plan_hemm

SCHEDULES = ("mo", "hoisted", "pallas", "sharded", "sharded_xla")
_CTX_CACHE: dict = {}


def _setup(name: str, shape=(4, 3, 5)):
    """Cached (ctx, plan) per parameter set — keygen once per module."""
    key = (name, shape)
    if key not in _CTX_CACHE:
        params = FAME_VERIFY_SETS[name]
        ctx = HEContext(CkksEngine(params), verify="error")
        plan = plan_hemm(ctx.eng, *shape)
        ctx.keygen(np.random.default_rng(0), rot_steps=plan.rot_steps)
        _CTX_CACHE[key] = (ctx, plan)
    return _CTX_CACHE[key]


# ---------------------------------------------------------------- acceptance

@pytest.mark.parametrize("name", sorted(FAME_VERIFY_SETS))
@pytest.mark.parametrize("schedule", SCHEDULES)
def test_verify_error_passes_every_schedule(name, schedule):
    """verify="error" admits every existing schedule on both fame sets,
    and a post-hoc full verification (components included) finds no
    error-severity diagnostics."""
    ctx, plan = _setup(name)
    prog = compile_hemm(ctx, plan, schedule=schedule)  # raises on rejection
    assert not errors(verify_program(prog))


@pytest.mark.parametrize("name", sorted(FAME_VERIFY_SETS))
def test_verify_error_passes_blockmm_with_hints(name):
    """Block MM with aliasing hints (shared A row / B column) verifies."""
    ctx, plan = _setup(name)
    gm, gl, gn = 2, 2, 2
    prog = compile_blockmm(
        ctx, plan, (gm, gl, gn), schedule="pallas",
        a_slots=[k for _ in range(gm) for k in range(gl)],
        b_slots=[k for k in range(gl) for _ in range(gn)])
    assert not errors(verify_program(prog))


# ------------------------------------------------- tracker vs real execution

@pytest.mark.parametrize("name", sorted(FAME_VERIFY_SETS))
def test_tracker_matches_execution_exactly(name):
    """The symbolic tracker's (level, scale) after a full hemm equals the
    executed program's output EXACTLY — the tracker mirrors core/ckks.py
    expression for expression, so no tolerance is needed."""
    ctx, plan = _setup(name)
    params = ctx.eng.params
    rng = np.random.default_rng(1)
    prog = compile_hemm(ctx, plan, schedule="mo")
    A = rng.uniform(-1, 1, (plan.m, plan.l))
    B = rng.uniform(-1, 1, (plan.l, plan.n))
    ctA = encrypt_matrix(ctx.eng, ctx.keys, A, rng)
    ctB = encrypt_matrix(ctx.eng, ctx.keys, B, rng)
    out = prog(ctA, ctB)
    tr = trace_hemm(ctx.eng.ctx.moduli_host, level=params.L,
                    scale_a=ctA.scale, scale_b=ctB.scale,
                    sigma_scale=plan.ds_sigma.scale,
                    tau_scale=plan.ds_tau.scale,
                    eps_scales=[d.scale for d in plan.ds_eps],
                    omega_scales=[d.scale for d in plan.ds_omega])
    assert tr.ok
    assert out.level == tr.out.level
    assert out.scale == tr.out.scale    # exact float equality, deliberate


def test_tracker_matches_execution_property():
    """Property test (hypothesis): random shapes on both fame sets — the
    trace's level AND scale equal the executed hemm's, exactly."""
    pytest.importorskip(
        "hypothesis",
        reason="property tests need hypothesis (requirements-dev.txt)")
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=6, deadline=None)
    @given(name=st.sampled_from(sorted(FAME_VERIFY_SETS)),
           m=st.integers(1, 4), l=st.integers(1, 4), n=st.integers(1, 4))
    def check(name, m, l, n):
        ctx, plan = _setup(name, shape=(m, l, n))
        params = ctx.eng.params
        rng = np.random.default_rng(m * 16 + l * 4 + n)
        prog = compile_hemm(ctx, plan, schedule="mo")
        ctA = encrypt_matrix(ctx.eng, ctx.keys,
                             rng.uniform(-1, 1, (m, l)), rng)
        ctB = encrypt_matrix(ctx.eng, ctx.keys,
                             rng.uniform(-1, 1, (l, n)), rng)
        out = prog(ctA, ctB)
        tr = trace_hemm(ctx.eng.ctx.moduli_host, level=params.L,
                        scale_a=ctA.scale, scale_b=ctB.scale,
                        sigma_scale=plan.ds_sigma.scale,
                        tau_scale=plan.ds_tau.scale,
                        eps_scales=[d.scale for d in plan.ds_eps],
                        omega_scales=[d.scale for d in plan.ds_omega])
        assert (out.level, out.scale) == (tr.out.level, tr.out.scale)

    check()


# ----------------------------------------------------------------- rejection

def test_chain_trace_flags_underflow():
    """LS pass: one hemm hop fits L=4 (depth 3), a deep chain does not —
    and the trace says so instead of tracing garbage."""
    ctx, plan = _setup("fame-s-rt")
    moduli = ctx.eng.ctx.moduli_host
    L = ctx.eng.params.L
    ok = trace_chain(moduli, [plan], level=L, scale=ctx.eng.params.scale)
    assert ok.ok and ok.out.level == L - 3
    bad = trace_chain(moduli, [plan] * 4, level=L,
                      scale=ctx.eng.params.scale)
    assert not bad.ok
    assert {d.rule for d in bad.diagnostics} <= {"LS001", "LS003"}
    assert any(d.rule in ("LS001", "LS003") for d in bad.diagnostics)


def test_compile_rejects_level_underflow():
    """A hemm compiled at level 2 cannot pay depth 3 — verify="error"
    rejects it at compile time, before any execution."""
    ctx, plan = _setup("fame-s-rt")
    with pytest.raises(VerificationError) as ei:
        compile_hemm(ctx, plan, level=2, schedule="mo")
    assert {d.rule for d in ei.value.diagnostics} & {"LS001", "LS003"}
    # ... and the rejected program was never memoized under this ctx
    # (hemm memo key: (tag, plan, schedule, level, chunk, batched, verify))
    assert not any(k[0] == "hemm" and k[3] == 2
                   for k in ctx._compiled if isinstance(k, tuple))


def test_warn_mode_warns_and_compiles():
    """verify="warn" on the same broken program warns but still returns."""
    ctx, _ = _setup("fame-s-rt")
    wctx = HEContext(ctx.eng, keys=ctx.keys, verify="warn")
    plan = plan_hemm(wctx.eng, 4, 3, 5)
    with pytest.warns(VerificationWarning):
        prog = compile_hemm(wctx, plan, level=2, schedule="mo")
    assert prog is not None


def test_jaxpr_lint_rejects_two_collective_program():
    """JX pass: a sharded body with an extra psum and an all_gather breaks
    the sole-collective contract (DESIGN.md §4) on both counts."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    from jax.sharding import Mesh, PartitionSpec as P

    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1), ("model",))

    def bad(x):
        y = jax.lax.psum(x, "model")
        z = jax.lax.psum(y * 2.0, "model")
        return jax.lax.all_gather(z, "model")

    f = jax.shard_map(bad, mesh=mesh, in_specs=P(), out_specs=P(None),
                      check_vma=False)
    diags = lint_jaxpr(jax.make_jaxpr(f)(jnp.ones(4)),
                       datapath="xla", expected_psums=2,
                       program="test", stage="sharded[xla]")
    assert {d.rule for d in diags} == {"JX001"}
    assert any("all_gather" in d.message for d in diags)


def test_jaxpr_lint_rejects_missing_pallas_call():
    """JX002: datapath="pallas" promised a fused kernel in-shard."""
    jax = pytest.importorskip("jax")
    import jax.numpy as jnp
    diags = lint_jaxpr(jax.make_jaxpr(lambda x: x + 1.0)(jnp.ones(4)),
                       datapath="pallas", expected_psums=0,
                       program="test", stage="sharded[pallas]")
    assert "JX002" in {d.rule for d in diags}


def test_compile_rejects_over_budget_chunk():
    """VM pass: a context with a tiny VMEM headroom cannot admit the fused
    pallas kernel at any chunk — VM001 at compile time."""
    ctx, plan = _setup("fame-s-rt")
    tight = HEContext(ctx.eng, keys=ctx.keys, vmem_headroom=1e-6,
                      verify="error")
    with pytest.raises(VerificationError) as ei:
        compile_hlt(tight, plan.ds_sigma, level=ctx.eng.params.L,
                    schedule="pallas", rotation_chunk=4)
    assert {d.rule for d in ei.value.diagnostics} == {"VM001"}


def test_stale_generation_flagged():
    """AR001: invalidating the context (arena eviction / key rotation)
    makes every previously compiled program verifiably stale."""
    params = FAME_VERIFY_SETS["fame-s-rt"]
    eng = _setup("fame-s-rt")[0].eng    # share the engine, own the keys
    ctx = HEContext(eng, verify="error")
    plan = plan_hemm(eng, 4, 3, 5)
    ctx.keygen(np.random.default_rng(2), rot_steps=plan.rot_steps)
    run = compile_hlt(ctx, [plan.ds_sigma, plan.ds_tau], level=params.L,
                      schedule="sharded", ct_slots=(0, 1))
    assert not errors(verify_program(run))
    ctx.invalidate()
    diags = verify_program(run)
    assert {d.rule for d in diags} == {"AR001"}


def test_diagnostic_rules_are_cataloged():
    """Every rule id the passes can emit is in RULES (and DESIGN.md §6 —
    tests/test_docs.py pins the doc side)."""
    for rule in ("LS001", "LS002", "LS003", "LS004", "JX001", "JX002",
                 "JX003", "VM001", "AR001", "AR002", "AR003", "AR004",
                 "VF000"):
        assert rule in RULES
    with pytest.raises(AssertionError):
        Diagnostic(rule="XX999", severity="error", program="p", stage="s",
                   message="m")


def test_scale_mismatch_add_flagged():
    """LS002: adding ciphertexts whose scales drifted apart is an error."""
    t = ScaleTracker([2.0**26] * 5, program="test")
    t.add(CtState(2, 2.0**26), CtState(2, 2.0**27), stage="acc")
    assert {d.rule for d in t.diagnostics} == {"LS002"}


# ------------------------------------------------------- serving cache key

def test_program_cache_keys_on_verify_mode():
    """Toggling ctx.verify must never return a program compiled under
    different checking — the cache key carries the mode."""
    from repro.serve.sessions import HEProgramCache, TenantSession
    ctx, plan = _setup("fame-s-rt")
    sess = TenantSession("t0", ctx)
    cache = HEProgramCache()
    level = ctx.eng.params.L
    p1 = cache.get(sess, plan, (1, 1, 1), level=level, schedule="mo")
    assert (cache.hits, cache.misses) == (0, 1)
    old = ctx.verify
    try:
        ctx.verify = "off"
        p2 = cache.get(sess, plan, (1, 1, 1), level=level, schedule="mo")
        assert (cache.hits, cache.misses) == (0, 2)
        assert p1 is not p2
        p3 = cache.get(sess, plan, (1, 1, 1), level=level, schedule="mo")
        assert cache.hits == 1 and p3 is p2
    finally:
        ctx.verify = old


def test_warn_never_breaks_on_verifier_crash(monkeypatch):
    """VF000: an internal verifier crash degrades to a warning in warn
    mode (the compile must survive) and propagates in error mode."""
    from repro.analysis import verify as verify_mod
    ctx, _ = _setup("fame-s-rt")

    def boom(prog, *, components=True):
        raise RuntimeError("pass exploded")

    monkeypatch.setattr(verify_mod, "verify_program", boom)
    wctx = HEContext(ctx.eng, keys=ctx.keys, verify="warn")
    plan = plan_hemm(wctx.eng, 4, 3, 5)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        prog = compile_hemm(wctx, plan, schedule="mo")
    assert prog is not None
    assert any("VF000" in str(w.message) for w in rec)
    ectx = HEContext(ctx.eng, keys=ctx.keys, verify="error")
    with pytest.raises(RuntimeError, match="pass exploded"):
        compile_hemm(ectx, plan_hemm(ectx.eng, 4, 3, 5), schedule="mo")
