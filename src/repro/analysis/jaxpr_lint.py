"""Jaxpr invariant linter — the verifier's JX pass over compiled programs.

Traces the compiled program's pipeline SHAPE-ONLY (ShapeDtypeStruct
arguments synthesized from the plan and the arena-resident operands — no
ciphertext data exists at compile time) and walks the jaxpr recursively
(``distributed/hlo_analysis.py``) to prove four invariants that were
previously only asserted in tests:

* JX001 — the merged ModDown+Rescale BaseConv psum is the SOLE collective:
  exactly the psum count of ``hlt_dist.expected_collectives`` (2 when the
  limb axis is sharded — one per output poly — else 0) and no other
  collective primitive anywhere in the program.
* JX002 — ``datapath="pallas"`` really lowers through the fused kernel:
  at least one ``pallas_call`` inside the shard.
* JX003 — no host round-trips in the hot path: no callback primitives.
* JX004 — full stage coverage: when the plan's ``datapath`` is "pallas"
  (the fused hoist/ModDown stages, DESIGN.md §7), NO XLA-lowered NTT/iNTT
  remains in the traced program.  The XLA transforms are named-jit wrappers
  (core/ntt.py ``NTT_EQN_NAMES``) so they census as ``jit`` eqns; the Pallas
  kernels call the unjitted ``*_raw`` recursions and contribute none.

Sharded programs lint their shard_map pipeline; single-device ``pallas``
programs lint the fused rotation+ModDown pipeline AND the hoist body.
"""
from __future__ import annotations

import jax

from repro.analysis.diagnostics import Diagnostic
from repro.core import hlt_dist
from repro.core.ntt import NTT_EQN_NAMES
from repro.distributed import hlo_analysis


def _named_ntt_count(jaxpr) -> int:
    """XLA-lowered NTT/iNTT eqns (named-jit ``jit`` markers) in a jaxpr."""
    n = 0
    for eqn in hlo_analysis.iter_jaxpr_eqns(jaxpr):
        if (eqn.primitive.name == "jit"
                and str(eqn.params.get("name")) in NTT_EQN_NAMES):
            n += 1
    return n


def lint_jaxpr(jaxpr, *, datapath: str, expected_psums: int,
               program: str = "hlt", stage: str = "sharded",
               stages: str = "xla") -> list:
    """JX diagnostics for one traced program jaxpr.  ``datapath`` is the
    kernel lowering ("pallas" = fused rotation kernel expected, JX002);
    ``stages`` is the hoist/ModDown stage coverage ("pallas" = no
    XLA-lowered NTT may remain, JX004)."""
    census = hlo_analysis.jaxpr_collective_census(jaxpr)
    diags = []
    if census["other_collectives"]:
        names = ", ".join(f"{k}×{v}" for k, v in
                          sorted(census["other_collectives"].items()))
        diags.append(Diagnostic(
            rule="JX001", severity="error", program=program, stage=stage,
            message=f"non-psum collective primitive(s) in the sharded "
                    f"program: {names}",
            hint="the merged ModDown+Rescale BaseConv psum must be the "
                 "only collective (DESIGN.md §4)"))
    if census["psums"] != expected_psums:
        diags.append(Diagnostic(
            rule="JX001", severity="error", program=program, stage=stage,
            message=f"{census['psums']} psum(s) in the sharded program, "
                    f"expected exactly {expected_psums} (one merged "
                    f"ModDown+Rescale per output poly)",
            hint="route all cross-device reduction through "
                 "hlt_dist.make_mod_down"))
    if datapath == "pallas" and census["pallas_calls"] < 1:
        diags.append(Diagnostic(
            rule="JX002", severity="error", program=program, stage=stage,
            message="datapath='pallas' but no pallas_call in the traced "
                    "program — the fused kernel is not on the path",
            hint="check make_sharded_hlt_fn's datapath plumbing"))
    if census["callbacks"]:
        names = ", ".join(f"{k}×{v}" for k, v in
                          sorted(census["callbacks"].items()))
        diags.append(Diagnostic(
            rule="JX003", severity="error", program=program, stage=stage,
            message=f"host callback primitive(s) in the hot path: {names}",
            hint="hot-path code must stay on-device; move host work to "
                 "compile time"))
    if stages == "pallas":
        n_ntt = _named_ntt_count(jaxpr)
        if n_ntt:
            diags.append(Diagnostic(
                rule="JX004", severity="error", program=program, stage=stage,
                message=f"{n_ntt} XLA-lowered NTT/iNTT op(s) in a "
                        f"datapath='pallas' program — the hoist/ModDown "
                        f"stages are not fully fused",
                hint="route the base-change transforms through "
                     "kernels/basechange.py (HEContext.datapath plumbing)"))
    return diags


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype)


def synth_sharded_args(run):
    """ShapeDtypeStruct argument pytree for one sharded CompiledHLT,
    mirroring ``CompiledHLT._sharded_args`` without ciphertexts.

    The hoist layout is resolved the way execution will resolve it for a
    batch matching the compile-time ``ct_slots`` hint (all-distinct when no
    hint): "dedup" when the unique count fits a ct rank's batch share,
    "element" otherwise.  Returns ``(args, hoist_layout)``.
    """
    import numpy as np   # dtypes only

    plan = run.plan
    tabs, tab_arrays = run._sharded
    n = run.ctx.eng.params.N
    diag_tab = run._slot_tables["diag"]
    b_pad = diag_tab.shape[0]
    b_loc = b_pad // max(1, run.ctx.n_ct)
    batch = plan.batch if plan.batch is not None else 1
    uniq = plan.n_ct_slots if plan.n_ct_slots is not None else batch
    m_pad, lvl1 = tabs.M_pad, plan.level + 1
    shape_only = lambda a: _sds(a.shape, a.dtype)
    u, rk0, rk1, perms, is_id = run._operands
    common = dict(u=shape_only(u), rk0=shape_only(rk0), rk1=shape_only(rk1),
                  perms=shape_only(perms), is_id=shape_only(is_id),
                  tab=jax.tree.map(shape_only, tab_arrays))
    slots = shape_only(diag_tab)
    if run._datapath == "xla":
        return dict(c0f=_sds((b_pad, m_pad, n), np.uint32),
                    c1f=_sds((b_pad, m_pad, n), np.uint32),
                    c1rep=_sds((b_pad, lvl1, n), np.uint32),
                    slots=slots, **common), "dedup"
    hoist_layout = "element" if uniq > b_loc else "dedup"
    h = b_pad if hoist_layout == "element" else uniq
    return dict(c0u=_sds((h, m_pad, n), np.uint32),
                c1u=_sds((h, m_pad, n), np.uint32),
                c1rep=_sds((h, lvl1, n), np.uint32),
                ct_slots=_sds((b_pad,), np.int32),
                slots=slots, **common), hoist_layout


def sharded_jaxpr(run):
    """Shape-only jaxpr of a sharded CompiledHLT's SPMD pipeline (the same
    jitted fn execution will call, traced on synthesized avals)."""
    args, layout = synth_sharded_args(run)
    tabs, _ = run._sharded
    fn = run.ctx._sharded_pipeline(tabs, run.plan.d_pad, run.plan.nbeta,
                                   run._datapath, run.plan.chunk, layout,
                                   run.plan.datapath)
    return jax.make_jaxpr(fn)(args)


def pallas_jaxprs(run):
    """Shape-only jaxprs of a single-device ``schedule="pallas"``
    CompiledHLT: ``(pipeline_jaxpr, hoist_jaxpr)`` — the fused
    rotation+ModDown pipeline on synthesized avals, and the hoist body the
    execution path feeds it from (the plan's datapath decides whether both
    lower the base-change stages through kernels/basechange.py)."""
    import numpy as np   # dtypes only
    from repro.core import hlt as hlt_mod

    plan = run.plan
    eng = run.ctx.eng
    n = eng.params.N
    level, nbeta = plan.level, plan.nbeta
    m = len(eng.tools.digit_bases(level)[0][2])
    u32 = np.uint32
    shape_only = lambda a: _sds(a.shape, a.dtype)
    operands = tuple(shape_only(a) for a in run._operands)
    if plan.batch is None:
        fn = run.ctx._pallas_pipeline(level, plan.chunk, "single")
        args = (_sds((nbeta, m, n), u32), _sds((m, n), u32),
                _sds((m, n), u32)) + operands
    else:
        fn = run.ctx._pallas_pipeline(level, plan.chunk, "indexed")
        h = plan.n_ct_slots if plan.n_ct_slots is not None else plan.batch
        args = (_sds((h, nbeta, m, n), u32), _sds((h, m, n), u32),
                _sds((h, m, n), u32)) + operands + (
                _sds((plan.batch,), np.int32), shape_only(run._diag_slots))
    pipeline = jax.make_jaxpr(fn)(*args)
    hoist_body = hlt_mod._hoist_body(eng, level, plan.datapath)
    hoist = jax.make_jaxpr(hoist_body)(
        _sds((level + 1, n), u32), _sds((level + 1, n), u32))
    return pipeline, hoist


def lint_compiled_hlt(run, *, program: str = "hlt") -> list:
    """The full JX pass for one CompiledHLT: sharded schedules lint the
    shard_map SPMD pipeline; the single-device fused schedule lints the
    rotation+ModDown pipeline and the hoist body (reference schedules have
    no compiled program to lint)."""
    if run.plan.schedule.startswith("sharded"):
        tabs, _ = run._sharded
        expected = hlt_dist.expected_collectives(tabs)["psum"]
        return lint_jaxpr(sharded_jaxpr(run), datapath=run._datapath,
                          expected_psums=expected, program=program,
                          stage=f"sharded[{run._datapath}]",
                          stages=run.plan.datapath)
    if run.plan.schedule != "pallas":
        return []
    pipeline, hoist = pallas_jaxprs(run)
    diags = lint_jaxpr(pipeline, datapath="pallas", expected_psums=0,
                       program=program, stage="pallas[pipeline]",
                       stages=run.plan.datapath)
    diags += lint_jaxpr(hoist, datapath=run.plan.datapath,
                        expected_psums=0, program=program,
                        stage="pallas[hoist]", stages=run.plan.datapath)
    return diags
