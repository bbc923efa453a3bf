"""One rotation-key table, read by index: the context keeps every rotation
key once (``HEContext.key_table``) and the fused HLT kernel fetches each
rotation's digits and limb rows from it by a scalar-prefetched row index
(``kernels/fused_hlt.fused_hlt_indexed``), at every level.

Checked on a Table III type II shape, a tall operand times a short one
(m×l×n = 8×2×4: m = 4·l as at 64×16×64, ω sets of 13-14 diagonals), with
one limb per digit, so Step 2's level reads a subset of the table's digits
and limb rows. One compiled product serves every check: the kernels run in
interpret mode on the CPU, where compiling dominates."""
import numpy as np
import pytest

import repro  # noqa: F401
from repro.core import automorph
from repro.core.ckks import CkksEngine
from repro.core.compile import HEContext, compile_hemm, compile_hlt
from repro.core.hemm import (decrypt_matrix, diag_count_exact,
                             encrypt_matrix, plan_hemm)
from repro.core.params import toy_params

P = toy_params(logN=6, L=4, k=1, beta=5, scale_bits=28)
SHAPE = (8, 2, 4)


@pytest.fixture(scope="module")
def setup():
    rng = np.random.default_rng(15)
    ctx = HEContext(CkksEngine(P), verify="error")
    plan = plan_hemm(ctx.eng, *SHAPE)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    m, l, n = SHAPE
    A = rng.uniform(-1, 1, (m, l))
    B = rng.uniform(-1, 1, (l, n))
    ctA = encrypt_matrix(ctx.eng, ctx.keys, A, rng)
    ctB = encrypt_matrix(ctx.eng, ctx.keys, B, rng)
    prog = compile_hemm(ctx, plan)
    return dict(ctx=ctx, plan=plan, A=A, B=B, ctA=ctA, ctB=ctB, prog=prog,
                out=prog(ctA, ctB))


def test_type_ii_diagonal_counts():
    """Set-A type II (64×16×64): 3,103 ω and 79 ε diagonals in Step 2."""
    got = diag_count_exact(64, 16, 64)
    assert sum(got["omega"]) == 3103 and sum(got["eps"]) == 79
    assert set(got["omega"]) == {193, 194} and max(got["eps"]) == 5
    assert got["sigma"] == got["tau"] == 31


@pytest.mark.parametrize("step", ["step1", "step2"])
def test_key_table_hlt_bit_exact_vs_mo(setup, step):
    """Each batched pallas/pallas launch of the product, reading the key
    table, == the u64 ``mo`` oracle reading each key by galois element, bit
    for bit: Step 1 at the top level, Step 2 one level down, where it reads
    4 of the table's 5 digits and 5 of its 6 limb rows."""
    s = setup
    prog, ctx = s["prog"], s["ctx"]
    if step == "step1":
        run, items = prog._step1, [s["ctA"], s["ctB"]]
    else:
        run, items = prog._step2, prog._step2_items(s["ctA"], s["ctB"])
    plan = run.plan
    assert (plan.schedule, plan.datapath) == ("pallas", "pallas")
    assert plan.nbeta == plan.level + 1 <= ctx.keys.table.k0.shape[1]
    for it, ds, out in zip(items, run._diags, run(items), strict=True):
        want = compile_hlt(ctx, ds, level=plan.level, schedule="mo")(it)
        np.testing.assert_array_equal(np.asarray(out.c0), np.asarray(want.c0))
        np.testing.assert_array_equal(np.asarray(out.c1), np.asarray(want.c1))
        assert (out.level, out.scale) == (want.level, want.scale)


def test_type_ii_product_reads_one_key_table(setup):
    """A whole compiled product matches float64 A @ B, and both steps'
    launches hold the context's one key table, not key copies."""
    s = setup
    ctx = s["ctx"]
    m, _, n = SHAPE
    got = decrypt_matrix(ctx.eng, ctx.keys, s["out"], m, n)
    np.testing.assert_allclose(got, s["A"] @ s["B"], atol=0.05)
    table = ctx.key_table()
    galois = {automorph.galois_elt_rot(r, P.N) for r in s["plan"].rot_steps}
    assert table.mont and table.k0.shape[0] == len(galois)
    for step in (s["prog"]._step1, s["prog"]._step2):
        k0, k1, u_m, perms, is_id, key_idx = step._operands
        assert k0 is table.k0 and k1 is table.k1
        assert u_m.shape[:2] == key_idx.shape == (step.plan.n_diag_slots,
                                                  step.plan.d_pad)
    assert [k[0] for k in ctx.arena._entries].count("key_table") == 1


def test_operand_bytes_count_table_rows_and_diagonals(setup):
    """``operand_bytes`` = the key-table bytes of the launch's distinct keys
    (this level's digits and rows) + its stacked diagonal operands, by
    formula; more padding adds slots and diagonal bytes, never key bytes."""
    s = setup
    ctx, eng = s["ctx"], s["ctx"].eng
    level = P.L - 1
    sets = list(s["plan"].ds_eps) + list(s["plan"].ds_omega)
    M, N, S = level + 1 + P.k, P.N, len(sets)
    keys = {automorph.galois_elt_rot(z, N) for ds in sets for z in ds.zs
            if z != 0}
    d_max = max(ds.d for ds in sets)
    plans = [compile_hlt(ctx, sets, level=level, schedule="pallas",
                         rotation_chunk=c).plan for c in (d_max, d_max - 1)]
    for plan in plans:
        assert plan.key_bytes == len(keys) * 2 * (level + 1) * M * N * 4
        assert plan.diag_bytes == S * plan.d_pad * (M * N * 4 + N * 4 + 4 + 4)
        assert plan.operand_bytes == plan.key_bytes + plan.diag_bytes
        assert plan.slots == S * plan.d_pad
        assert plan.key_rotations == sum(
            1 for ds in sets for z in ds.zs if z != 0)
        stats = plan.launch_stats()
        assert stats["padding"] == plan.slots - sum(ds.d for ds in sets)
    tight, padded = plans
    assert padded.d_pad > tight.d_pad
    assert padded.key_bytes == tight.key_bytes
    assert padded.diag_bytes > tight.diag_bytes
