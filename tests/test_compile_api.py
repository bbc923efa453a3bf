"""Plan/compile/execute API (core/compile.py): compiled-object reuse is
bit-exact vs a fresh compile, the operand arena stores exactly ONE copy of
shared tensors (slot count == unique operands, not batch size), re-keygen
invalidates every cached operand/pipeline, the deprecated ``schedule=`` shims
warn AND match the new API bit-exactly, and a dropped engine's recycled id
can never serve a stale jitted pipeline (the old _MO_JIT_CACHE bug)."""
import warnings

import numpy as np
import pytest

import repro  # noqa: F401
from repro.core import hlt as hlt_mod
from repro.core.ckks import CkksEngine
from repro.core.compile import (HEContext, compile_hemm, compile_hlt,
                                legacy_context)
from repro.core.hemm import plan_hemm, encrypt_matrix, decrypt_matrix
from repro.core.hlt import hoist, hoist_batched
from repro.core.params import toy_params

TOY = toy_params(logN=6, L=4, k=3, beta=2, scale_bits=26)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(7)
    ctx = HEContext(CkksEngine(TOY))
    m, l, n = 4, 3, 5
    plan = plan_hemm(ctx.eng, m, l, n)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    A = rng.uniform(-1, 1, (m, l))
    B = rng.uniform(-1, 1, (l, n))
    return dict(ctx=ctx, rng=rng, plan=plan, A=A, B=B, shape=(m, l, n),
                ctA=encrypt_matrix(ctx.eng, ctx.keys, A, rng),
                ctB=encrypt_matrix(ctx.eng, ctx.keys, B, rng))


def _assert_ct_equal(a, b):
    np.testing.assert_array_equal(np.asarray(a.c0), np.asarray(b.c0))
    np.testing.assert_array_equal(np.asarray(a.c1), np.asarray(b.c1))
    assert a.level == b.level and a.scale == b.scale


# -- compiled-object reuse -----------------------------------------------


def test_compile_memo_returns_same_object(setup):
    s = setup
    r1 = compile_hlt(s["ctx"], s["plan"].ds_sigma, level=s["ctA"].level)
    r2 = compile_hlt(s["ctx"], s["plan"].ds_sigma, level=s["ctA"].level)
    assert r1 is r2
    p1 = compile_hemm(s["ctx"], s["plan"])
    p2 = compile_hemm(s["ctx"], s["plan"])
    assert p1 is p2


def test_compiled_reuse_bit_exact_vs_fresh_compile(setup):
    """Reusing one CompiledHLT across calls == compiling fresh on a NEW
    context over the same engine/keys, bit for bit."""
    s = setup
    ctx = s["ctx"]
    run = compile_hlt(ctx, s["plan"].ds_sigma, level=s["ctA"].level)
    first = run(s["ctA"])
    again = run(s["ctA"])                       # reuse: warm arena + jit
    fresh_ctx = HEContext(ctx.eng, ctx.keys)    # cold arena + jit
    fresh = compile_hlt(fresh_ctx, s["plan"].ds_sigma,
                        level=s["ctA"].level)(s["ctA"])
    _assert_ct_equal(first, again)
    _assert_ct_equal(first, fresh)


def test_hemm_program_correct_and_schedules_bit_exact(setup):
    s = setup
    m, l, n = s["shape"]
    prog = compile_hemm(s["ctx"], s["plan"])
    assert prog.plan.schedule == "pallas"       # cost-model pick on TOY
    ctC = prog(s["ctA"], s["ctB"])
    got = decrypt_matrix(s["ctx"].eng, s["ctx"].keys, ctC, m, n)
    np.testing.assert_allclose(got, s["A"] @ s["B"], atol=0.05)
    for sched in ("mo", "hoisted"):
        alt = compile_hemm(s["ctx"], s["plan"], schedule=sched)
        _assert_ct_equal(alt(s["ctA"], s["ctB"]), ctC)


# -- operand arena dedup --------------------------------------------------


def test_arena_one_slot_per_unique_operand(setup):
    """Batched compile over B items with S unique DiagSets stores S operand
    slots — NOT B — in ONE arena entry for the launch, beside the context's
    one rotation-key table."""
    s = setup
    ctx = HEContext(s["ctx"].eng, s["ctx"].keys)    # fresh arena to count
    plan = s["plan"]
    diags = [plan.ds_sigma, plan.ds_tau, plan.ds_sigma, plan.ds_sigma,
             plan.ds_tau]                            # B=5, unique=2
    run = compile_hlt(ctx, diags, level=s["ctA"].level, schedule="pallas")
    assert run.plan.batch == 5
    assert run.plan.n_diag_slots == 2
    assert run.plan.diag_slots == (0, 1, 0, 0, 1)
    assert sorted(k[0] for k in ctx.arena._entries) == ["hlt_operands",
                                                        "key_table"]
    k0, k1, u_m, perms, is_id, key_idx = run._operands
    assert k0 is ctx.key_table().k0                  # the table, not a copy
    assert u_m.shape[0] == perms.shape[0] == key_idx.shape[0] == 2
    assert run.plan.operand_bytes_naive > run.plan.operand_bytes
    # a second program over the same launch adds NO arena entries; one over
    # another launch adds its own operands and reads the same key table
    compile_hlt(ctx, [plan.ds_sigma, plan.ds_tau], level=s["ctA"].level,
                schedule="pallas")
    assert len(ctx.arena) == 2
    other = compile_hlt(ctx, [plan.ds_tau, plan.ds_sigma],
                        level=s["ctA"].level, schedule="pallas")
    assert len(ctx.arena) == 3
    assert other._operands[0] is k0
    # execution is bit-exact vs singles, with repeated cts deduped too
    items = [s["ctA"], s["ctB"], s["ctA"], s["ctB"], s["ctA"]]
    outs = run(items)
    for it, ds, out in zip(items, diags, outs):
        single = compile_hlt(ctx, ds, level=it.level, schedule="pallas")(it)
        _assert_ct_equal(out, single)


def test_hemm_step2_stores_two_hoist_slots(setup):
    """hemm Step-2 runs 2·l HLTs off exactly 2 unique hoisting products."""
    s = setup
    plan = s["plan"]
    prog = compile_hemm(s["ctx"], plan)
    step2 = prog._step2
    assert step2.plan.batch == 2 * plan.l
    # the executed batch reuses each hoisted Step-1 output l times -> 2 slots
    ctA0, ctB0 = prog._step1([s["ctA"], s["ctB"]])
    h1, h2 = hoist_batched(s["ctx"].eng, [ctA0, ctB0])
    hoisted, ct_slots = step2._hoist_items([h1] * plan.l + [h2] * plan.l)
    assert len(hoisted) == 2
    assert ct_slots == [0] * plan.l + [1] * plan.l


def test_sharded_step2_hoist_slot_accounting(setup):
    """Under schedule="sharded" hemm Step-2 stores ONE hoisting product per
    unique input ciphertext (2, not 2·l): the ct_slots hint is canonical on
    the plan, hoist bytes reflect the dedup, the slot tables live in the
    arena, and the packed SPMD args stack exactly 2 unique ciphertexts.
    The pre-fusion baseline ("sharded_xla") re-hoists per element (2·l)."""
    import numpy as np
    s = setup
    plan = s["plan"]
    ctx = HEContext(s["ctx"].eng, s["ctx"].keys)    # fresh arena to inspect
    prog = compile_hemm(ctx, plan, schedule="sharded", rotation_chunk=2)
    s2 = prog._step2.plan
    assert s2.batch == 2 * plan.l
    assert s2.ct_slots == (0,) * plan.l + (1,) * plan.l
    assert s2.n_ct_slots == 2
    eng = ctx.eng
    m_ext = len(eng.tools.digit_bases(s2.level)[0][2])
    h_unit = (s2.nbeta + 2) * m_ext * 4 * eng.params.N
    assert s2.hoist_bytes == 2 * h_unit             # 2 unique products...
    assert s2.hoist_bytes_naive == 2 * plan.l * h_unit   # ...was 2·l
    assert prog.plan.hoist_bytes < prog.plan.hoist_bytes_naive
    kinds = {k[0] for k in ctx.arena._entries}
    assert "sharded_slot_tables" in kinds           # arena-owned slot tables
    # the packed shard_map args stack only the UNIQUE ciphertexts and route
    # batch elements through the ct-slot vector
    ctA0, ctB0 = prog._step1([s["ctA"], s["ctB"]])
    args, layout = prog._step2._sharded_args([ctA0] * plan.l + [ctB0] * plan.l)
    assert layout == "dedup"
    assert args["c0u"].shape[0] == args["c1rep"].shape[0] == 2
    np.testing.assert_array_equal(
        np.asarray(args["ct_slots"]), [0] * plan.l + [1] * plan.l)
    # the XLA baseline keeps the per-element layout: no dedup, 2·l hoists
    progx = compile_hemm(ctx, plan, schedule="sharded_xla")
    s2x = progx._step2.plan
    assert s2x.hoist_bytes == s2x.hoist_bytes_naive == 2 * plan.l * h_unit
    argsx, _ = progx._step2._sharded_args([ctA0] * plan.l + [ctB0] * plan.l)
    assert argsx["c1rep"].shape[0] == 2 * plan.l


def test_hoist_batched_bit_exact_vs_loop(setup):
    s = setup
    eng = s["ctx"].eng
    batched = hoist_batched(eng, [s["ctA"], s["ctB"], s["ctA"]])
    for ct, hb in zip([s["ctA"], s["ctB"], s["ctA"]], batched):
        hs = hoist(eng, ct)
        np.testing.assert_array_equal(np.asarray(hb.digits),
                                      np.asarray(hs.digits))
        np.testing.assert_array_equal(np.asarray(hb.c0_ext),
                                      np.asarray(hs.c0_ext))
        np.testing.assert_array_equal(np.asarray(hb.c1_ext),
                                      np.asarray(hs.c1_ext))
        assert hb.level == hs.level and hb.scale == hs.scale


# -- invalidation ---------------------------------------------------------


def test_keygen_invalidates_and_gives_fresh_results():
    rng = np.random.default_rng(11)
    ctx = HEContext(CkksEngine(TOY))
    m, l, n = 4, 3, 5
    plan = plan_hemm(ctx.eng, m, l, n)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    A = np.random.default_rng(1).uniform(-1, 1, (m, l))
    ct = encrypt_matrix(ctx.eng, ctx.keys, A, rng)
    run = compile_hlt(ctx, plan.ds_sigma, level=ct.level)
    run(ct)                                     # warm arena + pipelines
    assert len(ctx.arena) > 0
    old_keys = ctx.keys
    ctx.keygen(np.random.default_rng(99), rot_steps=plan.rot_steps)
    assert ctx.keys is not old_keys
    assert len(ctx.arena) == 0 and not ctx._compiled and not ctx._jit
    # the pre-keygen compiled object must refuse to run (stale operands)
    with pytest.raises(RuntimeError, match="stale compiled object"):
        run(ct)
    # fresh compile under the new keys matches the mo oracle AND decrypts
    ct2 = encrypt_matrix(ctx.eng, ctx.keys, A, np.random.default_rng(2))
    run2 = compile_hlt(ctx, plan.ds_sigma, level=ct2.level)
    assert run2 is not run
    out = run2(ct2)
    oracle = compile_hlt(ctx, plan.ds_sigma, level=ct2.level,
                         schedule="mo")(ct2)
    _assert_ct_equal(out, oracle)
    from repro.core.hemm import u_sigma
    got = ctx.eng.decrypt_decode(out, ctx.keys).real[:m * l]
    np.testing.assert_allclose(got, u_sigma(m, l) @ A.flatten(order="F"),
                               atol=1e-2)


# -- deprecated shims -----------------------------------------------------


def test_shims_warn_and_match_new_api(setup):
    s = setup
    ctx, plan = s["ctx"], s["plan"]
    eng, keys = ctx.eng, ctx.keys
    new = compile_hlt(ctx, plan.ds_sigma, level=s["ctA"].level,
                      schedule="pallas")(s["ctA"])
    with pytest.warns(DeprecationWarning, match="compile_hlt"):
        old = hlt_mod.hlt(eng, s["ctA"], plan.ds_sigma, keys,
                          schedule="pallas")
    _assert_ct_equal(old, new)
    with pytest.warns(DeprecationWarning, match="compile_hlt"):
        old_b = hlt_mod.hlt_batched(
            eng, [(s["ctA"], plan.ds_sigma), (s["ctB"], plan.ds_tau)], keys,
            schedule="pallas")
    newr = compile_hlt(ctx, [plan.ds_sigma, plan.ds_tau],
                       level=s["ctA"].level, schedule="pallas")
    for o, nw in zip(old_b, newr([s["ctA"], s["ctB"]])):
        _assert_ct_equal(o, nw)
    from repro.core import hemm as hemm_mod
    prog = compile_hemm(ctx, plan, schedule="pallas")
    with pytest.warns(DeprecationWarning, match="compile_hemm"):
        old_mm = hemm_mod.hemm(eng, s["ctA"], s["ctB"], plan, keys,
                               schedule="pallas")
    _assert_ct_equal(old_mm, prog(s["ctA"], s["ctB"]))


def test_shim_baseline_ignores_hoisted(setup):
    """schedule='baseline' has no hoisting product; a supplied hoisted= must
    be ignored (old dispatch behavior), not crash the baseline path."""
    s = setup
    ctx, plan = s["ctx"], s["plan"]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        plain = hlt_mod.hlt(ctx.eng, s["ctA"], plan.ds_sigma, ctx.keys,
                            schedule="baseline")
        with_h = hlt_mod.hlt(ctx.eng, s["ctA"], plan.ds_sigma, ctx.keys,
                             schedule="baseline",
                             hoisted=hoist(ctx.eng, s["ctA"]))
    _assert_ct_equal(plain, with_h)


def test_vmem_headroom_threaded_and_chunk_pinnable(setup):
    """The VMEM headroom is a named HEContext knob (costmodel.VMEM_HEADROOM
    by default), recorded on every plan — and rotation_chunk=2 can be pinned
    explicitly instead of relying on the headroom guess."""
    from repro.core.costmodel import VMEM_HEADROOM
    s = setup
    assert s["ctx"].vmem_headroom == VMEM_HEADROOM
    run = compile_hlt(s["ctx"], s["plan"].ds_sigma, level=s["ctA"].level,
                      schedule="pallas", rotation_chunk=2)
    assert run.plan.chunk == 2
    assert run.plan.vmem_headroom == VMEM_HEADROOM
    ctx2 = HEContext(s["ctx"].eng, s["ctx"].keys, vmem_headroom=0.5)
    assert ctx2.vmem_headroom == 0.5
    run2 = compile_hlt(ctx2, s["plan"].ds_sigma, level=s["ctA"].level)
    assert run2.plan.vmem_headroom == 0.5
    _assert_ct_equal(run2(s["ctA"]), run(s["ctA"]))


def test_meshless_context_has_unit_mesh_axes(setup):
    """No mesh -> single-device cost-model inputs and no sharded auto-pick."""
    ctx = setup["ctx"]
    assert ctx.mesh is None and ctx.n_model == 1 and ctx.n_ct == 1
    prog = compile_hemm(ctx, setup["plan"])
    assert prog.plan.schedule == "pallas"
    assert prog.plan.collective_bytes == 0


def test_sharded_single_device_fallback_bit_exact(setup):
    """schedule="sharded" without a mesh runs the same SPMD body unsharded —
    bit-exact vs mo, and its tables live in the arena (generation-guarded)."""
    s = setup
    ctx = HEContext(s["ctx"].eng, s["ctx"].keys)
    run = compile_hlt(ctx, s["plan"].ds_sigma, level=s["ctA"].level,
                      schedule="sharded")
    mo = compile_hlt(ctx, s["plan"].ds_sigma, level=s["ctA"].level,
                     schedule="mo")
    _assert_ct_equal(run(s["ctA"]), mo(s["ctA"]))
    kinds = {k[0] for k in ctx.arena._entries}
    assert "sharded_tables" in kinds            # arena-owned, not module state
    ctx.invalidate()
    with pytest.raises(RuntimeError, match="stale compiled object"):
        run(s["ctA"])


def test_legacy_context_pool_bounded():
    from repro.core import compile as compile_mod
    rng = np.random.default_rng(0)
    for i in range(compile_mod._LEGACY_POOL_MAX + 3):
        eng = CkksEngine(TOY)
        keys = eng.keygen(rng)
        legacy_context(eng, keys)
    assert len(compile_mod._LEGACY_CONTEXTS) <= compile_mod._LEGACY_POOL_MAX


def test_secure_engine_schedule_kwarg_warns():
    from repro.secure import SecureMatmulEngine
    with pytest.warns(DeprecationWarning, match="deprecated"):
        eng = SecureMatmulEngine(TOY, tile=4, schedule="pallas")
    assert eng.schedule == "pallas"
    auto = SecureMatmulEngine(TOY, tile=4)      # no warning path
    assert auto.schedule == "pallas"            # cost-model pick on TOY
    assert auto.batched


# -- engine identity regression (the id(eng) cache bug) -------------------


def test_engine_drop_and_recreate_never_serves_stale_pipeline():
    """The old module-level jit caches were keyed by id(engine); a GC'd
    engine's id could be recycled by a new engine with DIFFERENT moduli and
    silently serve a stale pipeline.  The context pool holds strong
    references, so recycled ids cannot alias; every recreated engine must
    produce oracle-exact results."""
    params = [toy_params(logN=6, L=4, k=3, beta=2, scale_bits=26),
              toy_params(logN=6, L=5, k=2, beta=3, scale_bits=26)]
    m, l = 4, 3
    for trial in range(4):
        p = params[trial % 2]
        rng = np.random.default_rng(100 + trial)
        eng = CkksEngine(p)
        plan = plan_hemm(eng, m, l, 5)
        keys = eng.keygen(rng, rot_steps=plan.rot_steps)
        A = rng.uniform(-1, 1, (m, l))
        ct = encrypt_matrix(eng, keys, A, rng)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            got_mo = hlt_mod.hlt(eng, ct, plan.ds_sigma, keys, schedule="mo")
            got_pl = hlt_mod.hlt(eng, ct, plan.ds_sigma, keys,
                                 schedule="pallas")
        oracle = hlt_mod._hlt_hoisted(eng, hoist(eng, ct), plan.ds_sigma,
                                      keys)
        _assert_ct_equal(got_mo, oracle)
        _assert_ct_equal(got_pl, oracle)
        # pooled contexts pin engines: same (eng, keys) -> same context,
        # distinct engines -> distinct contexts even if Python recycles ids
        assert legacy_context(eng, keys) is legacy_context(eng, keys)
        del eng, keys, ct, plan                 # drop our refs; pool keeps its


# -- ct_slots aliasing-hint mismatch (degrades accounting, never correctness)


@pytest.mark.parametrize("schedule", ["pallas", "sharded"])
def test_ct_slots_wrong_hint_still_bit_exact(setup, schedule):
    """A compile-time aliasing hint that CONTRADICTS the call-time pattern
    must not change a single bit of the output: execution re-derives
    aliasing from object identity.  Both mismatch directions are driven —
    hint says 'aliased' but two DIFFERENT ciphertexts arrive, and hint says
    'distinct' but the SAME ciphertext arrives twice — on the fused and the
    sharded (single-device fallback) schedules."""
    s = setup
    ctx, plan = s["ctx"], s["plan"]
    lvl = s["ctA"].level
    ds = [plan.ds_sigma, plan.ds_sigma]
    truth = compile_hlt(ctx, ds, level=lvl, schedule=schedule)

    # hint claims one shared input; call passes two DIFFERENT ciphertexts
    lies_aliased = compile_hlt(ctx, ds, level=lvl, schedule=schedule,
                               ct_slots=(0, 0))
    got = lies_aliased([s["ctA"], s["ctB"]])
    want = truth([s["ctA"], s["ctB"]])
    for g, w in zip(got, want):
        _assert_ct_equal(g, w)

    # hint claims distinct inputs; call passes the SAME ciphertext twice
    lies_distinct = compile_hlt(ctx, ds, level=lvl, schedule=schedule,
                                ct_slots=(0, 1))
    got = lies_distinct([s["ctA"], s["ctA"]])
    want = truth([s["ctA"], s["ctA"]])
    for g, w in zip(got, want):
        _assert_ct_equal(g, w)


def test_ct_slots_wrong_hint_degrades_accounting_only(setup):
    """The hint sizes the PLAN's hoist-dedup accounting: an all-aliased lie
    budgets one hoisting product, an all-distinct lie budgets one per batch
    element (= the naive bound) — regardless of what arrives at call time."""
    s = setup
    ctx, plan = s["ctx"], s["plan"]
    lvl = s["ctA"].level
    ds = [plan.ds_sigma, plan.ds_sigma]
    aliased = compile_hlt(ctx, ds, level=lvl, schedule="pallas",
                          ct_slots=(0, 0))
    distinct = compile_hlt(ctx, ds, level=lvl, schedule="pallas",
                           ct_slots=(0, 1))
    assert aliased.plan.n_ct_slots == 1
    assert distinct.plan.n_ct_slots == 2
    # hoist bytes follow the hint: half the naive bound when it promises
    # full aliasing, equal to it when it promises none
    assert aliased.plan.hoist_bytes * 2 == aliased.plan.hoist_bytes_naive
    assert distinct.plan.hoist_bytes == distinct.plan.hoist_bytes_naive
    # the two compiles share operand slots either way (same DiagSet)
    assert aliased.plan.n_diag_slots == distinct.plan.n_diag_slots == 1
