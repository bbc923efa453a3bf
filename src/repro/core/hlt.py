"""Homomorphic Linear Transformation — the paper's bottleneck and contribution.

Five schedules, mathematically equivalent (verified bit-exactly in tests;
DESIGN.md §2 tabulates what each one fuses):

* ``baseline``  — Algorithm 1 / Fig. 2(A): coarse-grained rotation loop; every
  Rot runs a full KeySwitch (Decomp→ModUp→KeyIP→ModDown per rotation), and a
  Rescale at the end. Maximal intermediate-ciphertext traffic.

* ``hoisted``   — Algorithm 3: Decomp/ModUp hoisted out of the rotation loop
  (shared by all d rotations), DiagIP accumulates in the extended basis PQ_ℓ,
  and ONE merged ModDown+Rescale (PQ_ℓ → Q_{ℓ-1}) finishes the HLT.

* ``mo``        — MO-HLT / Fig. 2(B): same math as ``hoisted`` with the loop
  order inverted — **limb outer, rotation inner** — expressed as a lax.map
  over the extended limb axis on the u64 reference datapath. Per-limb working
  set is (β+1) limb rows (Eq. 24) when rotation_chunk=1.

* ``pallas``    — the same limb-outer schedule driven through the fused
  Automorph→KeyIP→DiagIP Pallas kernel (kernels/fused_hlt.py) on the u32
  Montgomery datapath: the diagonal plaintexts are converted to the
  Montgomery domain once per launch, the rotation keys once per keygen into
  the context's one key table, which the kernel reads by row index; d is
  padded up to a rotation-chunk multiple with zero-diagonal identity
  entries, and the chunk defaults to the cost model's VMEM budget
  (core/costmodel.py pick_rotation_chunk). Bit-exact vs ``mo``/``hoisted``.

* ``sharded``   — the multi-device shard_map program (core/hlt_dist.py):
  limbs over the mesh ``model`` axis, the ciphertext batch over
  ``pod``×``data``, each model rank driving its limb shard through the same
  fused Pallas kernel with a ct-slot-deduped in-program hoist; the merged
  ModDown+Rescale BaseConv psum is the only collective.  (``sharded_xla``
  is its pre-fusion baseline, kept for benchmarks.)

This module holds the HLT *math*: diagonal encoding, hoisting (single and
batched across the ciphertext axis), the reference schedule implementations,
and the Montgomery operand builder for the fused kernel.  The public entry
point is the plan → compile → execute API in ``core/compile.py``::

    ctx = HEContext(CkksEngine(params));  ctx.keygen(rng, rot_steps)
    run = compile_hlt(ctx, diags, level=ct.level)      # cost model runs ONCE
    ct_out = run(ct)                                   # compiled, reusable

``compile_hlt`` picks the schedule / rotation chunk / d-padding from the cost
model and returns a ``CompiledHLT`` with an inspectable ``.plan``; batched
compiles store each unique operand tensor ONCE in the context's arena and the
fused kernel gathers by slot index (kernels/fused_hlt.py fused_hlt_indexed).
All precompute is owned by the ``HEContext`` (nothing hides in module globals
or on DiagSet instances); ``ctx.invalidate()`` drops it after a re-keygen.

``hlt()`` / ``hlt_batched()`` below are thin DEPRECATED shims kept for the
old string-threaded call style; they build a context internally and delegate.

The a-part (c0) is "scale-raised" into PQ_ℓ (multiply by [P]_{q_i}, zero on
special limbs) so DiagIP can accumulate both output polys in the extended
basis and share the single final ModDown — this is how Algorithm 3's
``ModUp(a)`` is realized exactly without a BaseConv.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Optional, Sequence

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import automorph, modmath as mm
from repro.core.ckks import Ciphertext, CkksEngine, Keys, KeyTable, Plaintext
from repro.kernels import basechange, common, ops


@dataclasses.dataclass
class DiagSet:
    """Non-zero diagonals of a transformation matrix U, encoded over the FULL
    prime basis (sliceable to any level / extended basis)."""
    zs: tuple[int, ...]
    pt: jnp.ndarray                  # (d, M_total, N) eval-domain residues
    scale: float
    shape: tuple[int, int]           # U is (rows, cols)

    @property
    def d(self) -> int:
        return len(self.zs)


@dataclasses.dataclass
class Hoisted:
    """Hoisting product: reusable across every HLT applied to the same ct."""
    digits: jnp.ndarray              # (β', M_ext, N) eval, full extended basis
    c0_ext: jnp.ndarray              # (M_ext, N) eval, P·c0 (zeros on specials)
    c1_ext: jnp.ndarray              # (M_ext, N) eval, P·c1 (for the z=0 term)
    level: int
    scale: float


# ---------------------------------------------------------------------------
# diagonal encoding
# ---------------------------------------------------------------------------


def encode_diagonals(eng: CkksEngine, U: np.ndarray,
                     scale: Optional[float] = None) -> DiagSet:
    """Halevi–Shoup ambient-rotation decomposition: U·m = Σ_z u_z ⊙ ρ(m; z).

    u_z[i] = U[i, i+z] (zero elsewhere); exact when slots >= max(rows, cols)
    because out-of-range rotated slots read zero padding (DESIGN.md §2).
    """
    p = eng.params
    rows, cols = U.shape
    assert max(rows, cols) <= p.slots, (U.shape, p.slots)
    scale = p.scale if scale is None else scale
    full = list(range(p.num_total))
    zs, pts = [], []
    for z in range(-(rows - 1), cols):
        i0, i1 = max(0, -z), min(rows, cols - z)
        if i1 <= i0:
            continue
        i = np.arange(i0, i1)
        vals = U[i, i + z]
        if not np.any(vals != 0):
            continue
        vec = np.zeros(p.slots)
        vec[i] = vals
        zs.append(z)
        pts.append(eng.encode_to_basis(vec, full, scale))
    return DiagSet(zs=tuple(zs), pt=jnp.stack(pts), scale=scale,
                   shape=(rows, cols))


# ---------------------------------------------------------------------------
# hoisting
# ---------------------------------------------------------------------------


def _hoist_body(eng: CkksEngine, level: int, datapath: Optional[str] = None):
    """Traceable (c0, c1) -> (digits, c0_ext, c1_ext) hoisting body at a fixed
    level — shared verbatim by hoist() and (under vmap) hoist_batched().

    datapath "pallas" runs Decomp→iNTT→ModUp-BaseConv→NTT as two fused
    pallas_calls (kernels/basechange.py) instead of the per-digit XLA chain;
    bit-exact vs it (tests/test_fused_datapath.py)."""
    p = eng.params
    dp = eng.datapath if datapath is None else datapath
    if dp == "pallas":
        tabs = eng.fused_hoist_tables(level)

        def body_fused(c0, c1):
            digs = basechange.hoist_fused(c1, tabs, interpret=ops._interp())
            return (digs, _scale_raise(eng, c0, level),
                    _scale_raise(eng, c1, level))

        return body_fused

    bases = eng.tools.digit_bases(level)
    full = bases[0][2]
    pos = {g: i for i, g in enumerate(full)}

    def body(c0, c1):
        digs = []
        for (own, gen, _) in bases:
            dig_eval = c1[own[0]: own[-1] + 1]
            coeff = eng._intt(dig_eval, eng.basis(own))
            ext = eng.tools.mod_up(coeff, own, gen)
            ext_eval = eng._ntt(ext, eng.basis(gen))
            x = jnp.zeros((len(full), p.N), dtype=jnp.uint32)
            x = x.at[np.array([pos[i] for i in own])].set(dig_eval)
            x = x.at[np.array([pos[i] for i in gen])].set(ext_eval)
            digs.append(x)
        return (jnp.stack(digs), _scale_raise(eng, c0, level),
                _scale_raise(eng, c1, level))

    return body


def hoist(eng: CkksEngine, ct: Ciphertext,
          datapath: Optional[str] = None) -> Hoisted:
    """Decomp + ModUp once (Algorithm 3 lines 1–2)."""
    digits, c0e, c1e = _hoist_body(eng, ct.level, datapath)(ct.c0, ct.c1)
    return Hoisted(digits=digits, c0_ext=c0e, c1_ext=c1e,
                   level=ct.level, scale=ct.scale)


def hoist_batched(eng: CkksEngine, cts: Sequence[Ciphertext], *,
                  datapath: Optional[str] = None,
                  double_buffer: bool = True) -> list:
    """Decomp + ModUp across the ciphertext axis: N hoisting products as ONE
    vmapped pipeline instead of a per-ciphertext Python loop (the last such
    loop in the batched block-MM path).  All cts must share one level.
    Bit-exact vs a loop of hoist() calls (same traced body, vmapped).

    On the "pallas" datapath with >1 ct the digits run through the
    double-buffered hoist kernel (kernels/basechange.py hoist_db): one grid
    step per ciphertext, ct i+1's DMA overlapping ct i's transform."""
    cts = list(cts)
    if not cts:
        return []
    levels = {ct.level for ct in cts}
    assert len(levels) == 1, f"hoist_batched needs one common level: {levels}"
    level = cts[0].level
    dp = eng.datapath if datapath is None else datapath
    if len(cts) == 1:
        return [hoist(eng, cts[0], dp)]
    c0s = jnp.stack([ct.c0 for ct in cts])
    c1s = jnp.stack([ct.c1 for ct in cts])
    if dp == "pallas" and double_buffer:
        tabs = eng.fused_hoist_tables(level)
        digits = basechange.hoist_fused_db(c1s, tabs,
                                           interpret=ops._interp())
        raise_b = jax.vmap(lambda x: _scale_raise(eng, x, level))
        c0e, c1e = raise_b(c0s), raise_b(c1s)
    else:
        digits, c0e, c1e = jax.vmap(_hoist_body(eng, level, dp))(c0s, c1s)
    return [Hoisted(digits=digits[b], c0_ext=c0e[b], c1_ext=c1e[b],
                    level=level, scale=ct.scale)
            for b, ct in enumerate(cts)]


def _scale_raise(eng: CkksEngine, x, ell: int):
    """x (ℓ+1, N) over Q_ℓ  ->  P·x over Q_ℓ ∪ P (zeros on special limbs)."""
    p = eng.params
    Pprod = 1
    for i in range(p.num_main, p.num_total):
        Pprod *= eng.ctx.moduli_host[i]
    pres = np.array([Pprod % eng.ctx.moduli_host[i] for i in range(ell + 1)],
                    dtype=np.uint64)[:, None]
    view = eng.main_basis(ell)
    top = mm.mulmod(x, jnp.asarray(pres).astype(jnp.uint32), view.moduli)
    return jnp.concatenate(
        [top, jnp.zeros((p.k, p.N), dtype=jnp.uint32)], axis=0)


def _gather_keys(eng: CkksEngine, keys: Keys, zs, nbeta: int, full):
    """Stack rot-key rows for the current basis: (d, β', M_ext, N) ×2.
    The z=0 entry (identity rotation) is never indexed; use zeros."""
    rows = np.asarray(full)
    k0s, k1s = [], []
    for z in zs:
        if z == 0:
            k0s.append(jnp.zeros((nbeta, len(full), eng.params.N), jnp.uint32))
            k1s.append(k0s[-1])
            continue
        g = automorph.galois_elt_rot(z, eng.params.N)
        key = keys.galois[g]
        k0s.append(key.k0[:nbeta][:, rows])
        k1s.append(key.k1[:nbeta][:, rows])
    return jnp.stack(k0s), jnp.stack(k1s)


def _perm_table(eng: CkksEngine, zs) -> np.ndarray:
    """(d, N) eval-domain automorph gather indices (identity for z=0)."""
    p = eng.params
    perms = []
    for z in zs:
        if z == 0:
            perms.append(np.arange(p.N, dtype=np.int64))
        else:
            perms.append(np.asarray(automorph.eval_perm(
                p.N, automorph.galois_elt_rot(z, p.N))))
    return np.stack(perms)


# ---------------------------------------------------------------------------
# schedules
# ---------------------------------------------------------------------------


# "sharded" is the multi-device shard_map schedule (core/hlt_dist.py): limbs
# over the mesh `model` axis, the ciphertext batch over `pod`×`data`, each
# model rank driving its limb shard through the fused Pallas kernel with a
# ct-slot-deduped in-program hoist; same math, bit-exact vs "mo"
# (tests/test_sharded.py).  "sharded_xla" is its pre-fusion baseline (XLA
# rotation scan, per-element hoist) kept for benchmarks — the cost model
# never selects it.
SCHEDULES = ("baseline", "hoisted", "mo", "pallas", "sharded", "sharded_xla")

_DEPRECATION = ("%s is deprecated: build an HEContext and use "
                "repro.core.compile.compile_hlt / compile_hemm (the "
                "plan/compile/execute API) instead.")


def hlt(eng: CkksEngine, ct: Ciphertext, diags: DiagSet, keys: Keys,
        schedule: str = "mo", rotation_chunk: Optional[int] = None,
        hoisted: Optional[Hoisted] = None) -> Ciphertext:
    """Ct' = Rescale( Σ_t u_{z_t} ⊙ Rot(Ct; z_t) )  — Algorithm 1's semantics.

    DEPRECATED shim: compiles through the plan/compile/execute API on an
    internally pooled HEContext. New code should call ``compile_hlt`` once and
    reuse the CompiledHLT."""
    warnings.warn(_DEPRECATION % "hlt()", DeprecationWarning, stacklevel=2)
    from repro.core.compile import compile_hlt, legacy_context
    # baseline has no hoisting product — it always re-rotates the full ct
    # (a supplied ``hoisted`` is ignored there, matching the old dispatch)
    item = ct if schedule == "baseline" or hoisted is None else hoisted
    run = compile_hlt(legacy_context(eng, keys), diags, level=item.level,
                      schedule=schedule, rotation_chunk=rotation_chunk)
    return run(item)


def hlt_batched(eng: CkksEngine, items: Sequence, keys: Keys,
                schedule: str = "pallas",
                rotation_chunk: Optional[int] = None) -> list:
    """Apply many HLTs as ONE batched pipeline over ``(ct_or_hoisted,
    DiagSet)`` pairs at a common level.

    DEPRECATED shim over ``compile_hlt(ctx, [ds...], level=...)``; the
    compiled path stores each unique hoisting product / diagonal set once
    (slot-indexed kernel) instead of stacking B-fold copies.

    Returns a list of Ciphertexts, one per item, in order.
    """
    warnings.warn(_DEPRECATION % "hlt_batched()", DeprecationWarning,
                  stacklevel=2)
    from repro.core.compile import compile_hlt, legacy_context
    items = list(items)
    levels = {it.level for it, _ in items}
    assert len(levels) == 1, f"hlt_batched needs one common level, got {levels}"
    run = compile_hlt(legacy_context(eng, keys), [ds for _, ds in items],
                      level=levels.pop(), schedule=schedule,
                      rotation_chunk=rotation_chunk)
    return run([it for it, _ in items])


def _hlt_baseline(eng: CkksEngine, ct, diags: DiagSet, keys: Keys) -> Ciphertext:
    p = eng.params
    ell = ct.level
    view = eng.main_basis(ell)
    acc: Optional[Ciphertext] = None
    for t, z in enumerate(diags.zs):
        rt = ct if z == 0 else eng.rotate(ct, z, keys)
        pt = Plaintext(diags.pt[t][: ell + 1], ell, diags.scale)
        term = eng.cmult(rt, pt)
        acc = term if acc is None else eng.add(acc, term)
    return eng.rescale(acc)


def _accumulate(eng, hst: Hoisted, diags: DiagSet, keys: Keys, full, view,
                t_indices, acc0, acc1):
    """Shared rotation-loop body (full-Ct-level, coarse ordering)."""
    nbeta = hst.digits.shape[0]
    p = eng.params
    rows = np.asarray(full)
    for t in t_indices:
        z = diags.zs[t]
        u = diags.pt[t][rows]
        if z == 0:
            acc0 = mm.addmod(acc0, mm.mulmod(u, hst.c0_ext, view.moduli), view.moduli)
            acc1 = mm.addmod(acc1, mm.mulmod(u, hst.c1_ext, view.moduli), view.moduli)
            continue
        g = automorph.galois_elt_rot(z, p.N)
        key = keys.galois[g]
        d_rot = automorph.apply_eval(hst.digits, p.N, g)
        c0_rot = automorph.apply_eval(hst.c0_ext, p.N, g)
        k0 = jnp.zeros_like(acc0)
        k1 = jnp.zeros_like(acc1)
        for j in range(nbeta):
            k0 = mm.addmod(k0, mm.mulmod(d_rot[j], key.k0[j][rows], view.moduli),
                           view.moduli)
            k1 = mm.addmod(k1, mm.mulmod(d_rot[j], key.k1[j][rows], view.moduli),
                           view.moduli)
        acc0 = mm.addmod(acc0, mm.mulmod(u, mm.addmod(k0, c0_rot, view.moduli),
                                         view.moduli), view.moduli)
        acc1 = mm.addmod(acc1, mm.mulmod(u, k1, view.moduli), view.moduli)
    return acc0, acc1


def _finish(eng: CkksEngine, hst: Hoisted, diags: DiagSet, acc0, acc1) -> Ciphertext:
    ell = hst.level
    c0 = eng._mod_down_eval(acc0, ell, drop_last=True)
    c1 = eng._mod_down_eval(acc1, ell, drop_last=True)
    q_ell = eng.ctx.moduli_host[ell]
    return Ciphertext(c0, c1, ell - 1, hst.scale * diags.scale / q_ell)


def _hlt_hoisted(eng: CkksEngine, hst: Hoisted, diags: DiagSet, keys: Keys) -> Ciphertext:
    full = eng.tools.digit_bases(hst.level)[0][2]
    view = eng.basis(full)
    acc0 = jnp.zeros((len(full), eng.params.N), dtype=jnp.uint32)
    acc1 = jnp.zeros_like(acc0)
    acc0, acc1 = _accumulate(eng, hst, diags, keys, full, view,
                             range(diags.d), acc0, acc1)
    return _finish(eng, hst, diags, acc0, acc1)


def _mo_pipeline(eng: CkksEngine, level: int, nbeta: int, d: int, chunk: int,
                 jit_cache: dict):
    """Jitted limb-outer pipeline (incl. merged ModDown+Rescale), memoized in
    the CALLER-OWNED ``jit_cache`` (an HEContext's) — never in a module
    global keyed by id(eng), which can silently alias a garbage-collected
    engine's id to a new engine with different moduli."""
    key = ("mo", level, nbeta, d, chunk)
    fn = jit_cache.get(key)
    if fn is not None:
        return fn
    p = eng.params
    full = eng.tools.digit_bases(level)[0][2]
    view = eng.basis(full)

    def pipeline(digits, c0e, c1e, u_all, rk0, rk1, perms, is_id):
        xs = dict(
            dig=jnp.swapaxes(digits, 0, 1),       # (M, β', N)
            c0e=c0e,                              # (M, N)
            c1e=c1e,
            u=jnp.swapaxes(u_all, 0, 1),          # (M, d, N)
            k0=jnp.swapaxes(rk0, 0, 2),           # (M, β', d, N)
            k1=jnp.swapaxes(rk1, 0, 2),
            q=view.moduli,                        # (M, 1)
        )

        def limb_body(x):
            q = x["q"]                            # (1,)
            a0 = jnp.zeros((p.N,), dtype=jnp.uint32)
            a1 = jnp.zeros_like(a0)
            for s in range(0, d, chunk):
                e = min(s + chunk, d)
                pm = perms[s:e]                   # (c, N)
                dig_rot = x["dig"][:, pm]         # (β', c, N) gather
                c0_rot = x["c0e"][pm]             # (c, N)
                k0 = jnp.zeros((e - s, p.N), dtype=jnp.uint32)
                k1 = jnp.zeros_like(k0)
                for j in range(nbeta):
                    k0 = mm.addmod(k0, mm.mulmod(dig_rot[j], x["k0"][j, s:e], q), q)
                    k1 = mm.addmod(k1, mm.mulmod(dig_rot[j], x["k1"][j, s:e], q), q)
                # z=0 entries bypass KeyIP: (P·c0, P·c1) directly
                sel = is_id[s:e][:, None]
                t0 = jnp.where(sel, x["c0e"][None], mm.addmod(k0, c0_rot, q))
                t1 = jnp.where(sel, x["c1e"][None], k1)
                u = x["u"][s:e]
                a0 = mm.addmod(a0, _reduce_add(mm.mulmod(u, t0, q), q), q)
                a1 = mm.addmod(a1, _reduce_add(mm.mulmod(u, t1, q), q), q)
            return a0, a1

        acc0, acc1 = jax.lax.map(limb_body, xs)
        c0 = eng._mod_down_eval(acc0, level, drop_last=True)
        c1 = eng._mod_down_eval(acc1, level, drop_last=True)
        return c0, c1

    fn = jax.jit(pipeline)
    jit_cache[key] = fn
    return fn


def _hlt_mo(eng: CkksEngine, hst: Hoisted, diags: DiagSet, keys: Keys,
            rotation_chunk: Optional[int], jit_cache: dict) -> Ciphertext:
    """Limb-outer / rotation-inner schedule over the extended basis."""
    full = eng.tools.digit_bases(hst.level)[0][2]
    nbeta = hst.digits.shape[0]
    rk0, rk1 = _gather_keys(eng, keys, diags.zs, nbeta, full)   # (d, β', M, N)
    perms = _perm_table(eng, diags.zs)                          # (d, N)
    u_all = diags.pt[:, np.asarray(full)]                       # (d, M, N)
    is_id = jnp.asarray(np.array([z == 0 for z in diags.zs]))   # (d,)
    d = diags.d
    chunk = d if rotation_chunk is None else max(1, min(rotation_chunk, d))
    fn = _mo_pipeline(eng, hst.level, nbeta, d, chunk, jit_cache)
    c0, c1 = fn(hst.digits, hst.c0_ext, hst.c1_ext, u_all, rk0, rk1,
                perms, is_id)
    q_ell = eng.ctx.moduli_host[hst.level]
    return Ciphertext(c0, c1, hst.level - 1,
                      hst.scale * diags.scale / q_ell)


def _reduce_add(x, q):
    """Sum (c, N) mod q along axis 0 in u64 (c·q < 2^63 safe)."""
    return (jnp.sum(x.astype(jnp.uint64), axis=0) % q).astype(jnp.uint32)


# ---------------------------------------------------------------------------
# pallas schedule: Montgomery operand builder for the fused kernel
# ---------------------------------------------------------------------------


def key_rows(eng: CkksEngine, level: int) -> np.ndarray:
    """(M_ext,) i32: the key-table limb row of each limb of the extended
    basis at ``level`` (the table holds the full basis)."""
    return np.asarray(eng.tools.digit_bases(level)[0][2], np.int32)


def _build_pallas_operands(eng: CkksEngine, diag_list, table: KeyTable,
                           level: int, d_pad: int):
    """Kernel operands of one launch over the UNIQUE DiagSets ``diag_list``,
    each padded to ``d_pad`` rotations and stacked over the sets:
    (u_m, perms, is_id, key_idx). PURE — ownership lives in the HEContext
    operand arena (core/compile.py), one entry per launch.

    u_m (S, d_pad, M, R, C) holds the Montgomery-domain diagonals as kernel
    tiles; key_idx (S, d_pad) i32 is each rotation's row of the context's
    rotation-key ``table`` — no key is copied here. Padding entries are
    identity rotations (perm = arange) with zero diagonal and is_id=1, so
    they bypass KeyIP, read no key (row 0 stands in) and contribute exactly
    zero to DiagIP.
    """
    p = eng.params
    rows = key_rows(eng, level)
    view = eng.basis(rows)
    q32, qneg, r2 = view.moduli_u32, view.qneg_inv, view.r2
    M = len(rows)
    us, perms, is_id, kidx = [], [], [], []
    for ds in diag_list:
        pad = d_pad - ds.d
        u = mm.to_mont(ds.pt[:, rows], q32, qneg, r2)        # (d, M, N)
        us.append(common.tiles(jnp.concatenate(
            [u, jnp.zeros((pad, M, p.N), jnp.uint32)]) if pad else u))
        perms.append(np.concatenate(
            [_perm_table(eng, ds.zs),
             np.tile(np.arange(p.N), (pad, 1))]).astype(np.int32))
        is_id.append([1 if z == 0 else 0 for z in ds.zs] + [1] * pad)
        kidx.append([0 if z == 0 else
                     table.rows[automorph.galois_elt_rot(z, p.N)]
                     for z in ds.zs] + [0] * pad)
    return (jnp.stack(us), jnp.asarray(np.stack(perms)),
            jnp.asarray(np.array(is_id, np.int32)[..., None]),
            jnp.asarray(np.array(kidx, np.int32)))


def _build_sharded_operands(eng: CkksEngine, diags: DiagSet, keys: Keys,
                            level: int, nbeta: int, d_pad: int):
    """Montgomery-domain operands of one DiagSet for the ``sharded``
    schedule, which keeps a rotation key copy per diagonal: (u_m, rk0_m,
    rk1_m, perms, is_id), padded to d_pad rotations like
    ``_build_pallas_operands``."""
    p = eng.params
    full = eng.tools.digit_bases(level)[0][2]
    rows = np.asarray(full)
    view = eng.basis(full)
    q32, qneg, r2 = view.moduli_u32, view.qneg_inv, view.r2
    rk0, rk1 = _gather_keys(eng, keys, diags.zs, nbeta, full)  # (d, β', M, N)
    u_all = diags.pt[:, rows]                                  # (d, M, N)
    u_m = mm.to_mont(u_all, q32, qneg, r2)
    rk0_m = mm.to_mont(rk0, q32, qneg, r2)
    rk1_m = mm.to_mont(rk1, q32, qneg, r2)
    perms = _perm_table(eng, diags.zs).astype(np.int32)        # (d, N)
    is_id = np.array([[1 if z == 0 else 0] for z in diags.zs], np.int32)
    d = diags.d
    if d_pad > d:
        pad = d_pad - d
        M = len(full)
        u_m = jnp.concatenate(
            [u_m, jnp.zeros((pad, M, p.N), jnp.uint32)], axis=0)
        zk = jnp.zeros((pad, nbeta, M, p.N), jnp.uint32)
        rk0_m = jnp.concatenate([rk0_m, zk], axis=0)
        rk1_m = jnp.concatenate([rk1_m, zk], axis=0)
        perms = np.concatenate(
            [perms, np.tile(np.arange(p.N, dtype=np.int32), (pad, 1))], axis=0)
        is_id = np.concatenate([is_id, np.ones((pad, 1), np.int32)], axis=0)
    return (u_m, rk0_m, rk1_m, jnp.asarray(perms), jnp.asarray(is_id))
