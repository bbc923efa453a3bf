"""Distributed MO-HLT: the paper's datapath as one SPMD program.

Mapping (distributed/sharding.py rules: ``limbs -> model``, ``ct_batch ->
pod x data``): RNS limbs shard over the `model` mesh axis (limbs are
independent through NTT/Automorph/KeyIP/DiagIP — the fused stages), ciphertext
batch shards over `pod`×`data`. BaseConv (ModUp/ModDown) is the only
limb-coupling stage → the only collective, exactly the paper's "only unfused
sub-operations incur off-chip traffic" translated to collective volume.

Arithmetic is the TPU-native u32 Montgomery path end to end (no u64), so the
lowered HLO is what a real v5e deployment would run. The float correction in
BaseConv is f32 on this path (f64 on the CPU oracle path) — configurable, and
the CPU test uses f64 to check bit-exactness against core/hlt.py's MO schedule.

Two entry points:

* ``build_tables`` + ``make_mo_hlt_fn`` — the original GSPMD prototype (one
  DiagSet applied to a ciphertext batch, sharding via constraint annotations).
  Kept for the roofline dry-run (launch/dryrun.py) and the slow SPMD test.

* ``build_shard_tables`` + ``make_sharded_hlt_fn`` — the production
  ``schedule="sharded"`` program behind ``compile_hlt``/``compile_hemm``
  (core/compile.py): an explicit ``shard_map`` SPMD program with per-element
  diagonal-set AND ciphertext slots (the same deduped operand layout as the
  fused Pallas schedule), ciphertext batch sharded over ``pod``×``data`` and
  the extended limb axis sharded over ``model`` (padded when the device count
  does not divide it). ModUp runs collective-free off the replicated inputs;
  the merged ModDown+Rescale BaseConv is the ONLY collective — an exact
  ``psum`` with a single contributor per limb row, so the program stays
  bit-exact against the single-device MO schedule.

  Two datapaths share the shard_map skeleton (``datapath=``):

  - ``"pallas"`` (the default) — each model rank drives its limb-row shard
    through the fused Automorph→KeyIP→DiagIP Pallas kernel
    (kernels/fused_hlt.py ``fused_hlt_indexed``), and the in-program hoist is
    CT-SLOT DEDUPED: the rank hoists each UNIQUE input ciphertext once and
    the kernel gathers digit rows by ``ct_slots[b]`` (hemm Step-2's
    ``[A0]·l + [B0]·l`` batch hoists 2 products per rank, not 2·l).  This
    stacks the paper's two wins — single-node datapath reuse and multi-unit
    limb partitioning — in one program (DESIGN.md §4).
  - ``"xla"`` — the PR-3 program kept verbatim as the fusion baseline:
    limb-local stages lower through plain XLA (a lax.scan over rotations)
    and every batch element re-hoists.  Exposed as
    ``schedule="sharded_xla"`` for benchmarks (fused-vs-XLA wall times,
    hoist bytes before/after dedup); the cost model never selects it.

This module owns NO table/cache state: every builder here is pure, and the
compiled path stores its tables in the owning ``HEContext`` operand arena
(generation-guarded, dropped on re-keygen) like every other operand.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import automorph, modmath as mm, ntt
from repro.core.params import HEParams, get_context
from repro.core.rns import RnsTools


# ---------------------------------------------------------------------------
# constant tables (host-built, baked into the jitted program)
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class DistTables:
    params: HEParams
    d: int
    full: tuple                    # prime indices [Q_L..., P...]
    q32: np.ndarray                # (M,1) u32
    qneg: np.ndarray               # (M,1)
    r2: np.ndarray                 # (M,1)
    psi_m: np.ndarray              # (M,N) mont twiddles
    psii_m: np.ndarray
    ninv_m: np.ndarray             # (M,1) mont
    perms: np.ndarray              # (d,N) int32
    p_raise_m: np.ndarray          # (L+1,1) [P]_{q_i} in mont form
    digits: list                   # per digit: dict(own, gen, tables...)
    md: dict                       # merged ModDown+Rescale tables
    ctb: int


# host Montgomery encoding: the shared modmath helper (was a local copy)
_mont = mm.to_mont_host_arr


def build_tables(params: HEParams, d: int, ctb: int) -> DistTables:
    ctx = get_context(params)
    tools = RnsTools(ctx)
    L, N = params.L, params.N
    full = tuple(range(L + 1)) + tuple(range(params.num_main, params.num_total))
    M = len(full)
    qs = np.array([ctx.moduli_host[i] for i in full], dtype=np.uint64)[:, None]
    q32 = qs.astype(np.uint32)
    qneg = np.empty((M, 1), np.uint32)
    r2 = np.empty((M, 1), np.uint32)
    for r_, i in enumerate(full):
        a, b = mm.mont_constants(ctx.moduli_host[i])
        qneg[r_, 0], r2[r_, 0] = a, b
    rows = np.asarray(full)
    psi_m = np.asarray(ctx.psi_brv_mont)[rows]
    psii_m = np.asarray(ctx.psi_inv_brv_mont)[rows]
    ninv_m = _mont(np.asarray(ctx.n_inv)[rows].astype(np.uint64), qs)

    # rotation permutations: z = -(d//2) .. +(d - d//2 - 1), 0 = identity
    zs = list(range(-(d // 2), d - d // 2))
    perms = np.stack([
        np.arange(N, dtype=np.int32) if z == 0 else
        np.asarray(automorph.eval_perm(
            N, automorph.galois_elt_rot(z, N)), dtype=np.int32)
        for z in zs])

    Pprod = 1
    for i in range(params.num_main, params.num_total):
        Pprod *= ctx.moduli_host[i]
    p_raise = np.array([Pprod % ctx.moduli_host[i] for i in range(L + 1)],
                       dtype=np.uint64)[:, None]
    p_raise_m = _mont(p_raise, qs[: L + 1])

    pos = {g: i for i, g in enumerate(full)}
    digits = []
    for own, gen, _ in tools.digit_bases(L):
        hat_inv, W, D_mod_t, inv_d = tools._bc_tables(own, gen)
        own_q = np.array([ctx.moduli_host[i] for i in own],
                         dtype=np.uint64)[:, None]
        gen_q = np.array([ctx.moduli_host[i] for i in gen],
                         dtype=np.uint64)[:, None]
        digits.append(dict(
            own_rows=np.array([pos[i] for i in own]),
            gen_rows=np.array([pos[i] for i in gen]),
            hat_inv_m=_mont(np.asarray(hat_inv, np.uint64), own_q),
            # W from _bc_tables is already (|gen|, |own|)
            W_m=_mont(np.asarray(W, np.uint64), gen_q)[:, :, None],
            D_mod_m=_mont(np.asarray(D_mod_t, np.uint64), gen_q),
            inv_d=np.asarray(inv_d, np.float64),
        ))

    # merged ModDown+Rescale: drop specials + q_L
    spec = tuple(range(params.num_main, params.num_total))
    P_ext = spec + (L,)
    Q_out = tuple(range(L))
    hat_inv, W, D_mod_t, inv_d = tools._bc_tables(P_ext, Q_out)
    pe_q = np.array([ctx.moduli_host[i] for i in P_ext],
                    dtype=np.uint64)[:, None]
    qo_q = np.array([ctx.moduli_host[i] for i in Q_out],
                    dtype=np.uint64)[:, None]
    p_inv = tools._moddown_tables(P_ext, Q_out)
    md = dict(
        drop_rows=np.array([pos[i] for i in P_ext]),
        out_rows=np.array([pos[i] for i in Q_out]),
        hat_inv_m=_mont(np.asarray(hat_inv, np.uint64), pe_q),
        W_m=_mont(np.asarray(W, np.uint64), qo_q)[:, :, None],
        D_mod_m=_mont(np.asarray(D_mod_t, np.uint64), qo_q),
        inv_d=np.asarray(inv_d, np.float64),
        p_inv_m=_mont(np.asarray(p_inv, np.uint64), qo_q),
    )
    return DistTables(params, d, full, q32, qneg, r2, psi_m, psii_m, ninv_m,
                      perms, p_raise_m, digits, md, ctb)


# ---------------------------------------------------------------------------
# mont building blocks (broadcast over leading ct-batch axis)
# ---------------------------------------------------------------------------


def _mod_reduce(x, q32, axis: int):
    """Tree-reduce modular sum along `axis` (shared impl: mm.montsum)."""
    return mm.montsum(x, q32, axis=axis)


def _base_conv_mont(x, t, fp_dtype):
    """x: (..., |own|, N) coeff std-domain. Returns (..., |gen|, N)."""
    q_own, q_gen = t["q_own"], t["q_gen"]          # (|own|,1), (|gen|,1)
    y = mm.montmul(x, t["hat_inv_m"], q_own, t["qneg_own"])
    v = jnp.floor(jnp.sum(y.astype(fp_dtype) * t["inv_d"].astype(fp_dtype),
                          axis=-2) + 0.5e-6).astype(jnp.uint32)  # (..., N)
    prod = mm.montmul(y[..., None, :, :], t["W_m"], q_gen[..., None, :],
                      t["qneg_gen"][..., None, :])  # (..., |gen|, |own|, N)
    acc = _mod_reduce(prod, q_gen[..., None, :], axis=-2)
    corr = mm.montmul(v[..., None, :], t["D_mod_m"], q_gen, t["qneg_gen"])
    return mm.montsub(acc, corr, q_gen)


def _mk_bc_tables(tabs: DistTables, spec: dict):
    own = spec.get("own_rows", spec.get("drop_rows"))
    gen = spec.get("gen_rows", spec.get("out_rows"))
    return dict(
        hat_inv_m=jnp.asarray(spec["hat_inv_m"]),
        W_m=jnp.asarray(spec["W_m"]),
        D_mod_m=jnp.asarray(spec["D_mod_m"]),
        inv_d=jnp.asarray(spec["inv_d"]),
        q_own=jnp.asarray(tabs.q32[own]), qneg_own=jnp.asarray(tabs.qneg[own]),
        q_gen=jnp.asarray(tabs.q32[gen]), qneg_gen=jnp.asarray(tabs.qneg[gen]),
    )


# ---------------------------------------------------------------------------
# the SPMD MO-HLT program
# ---------------------------------------------------------------------------


def make_mo_hlt_fn(tabs: DistTables, rules=None, fp_dtype=jnp.float32,
                   unroll: int = 1):
    """Returns fn(c0, c1, u_mont, rk0_mont, rk1_mont) -> (c0', c1').

    c0, c1: (CTB, L+1, N) u32 std-domain eval.
    u_mont: (d, M, N); rk{0,1}_mont: (d, β, M, N) — Montgomery domain.
    Output: (CTB, L, N) ×2 (one level consumed — merged ModDown+Rescale)."""
    p = tabs.params
    L, N, M = p.L, p.N, len(tabs.full)
    nb = len(tabs.digits)
    q32 = jnp.asarray(tabs.q32)
    qneg = jnp.asarray(tabs.qneg)
    psi_m, psii_m = jnp.asarray(tabs.psi_m), jnp.asarray(tabs.psii_m)
    ninv_m = jnp.asarray(tabs.ninv_m)
    perms = jnp.asarray(tabs.perms)
    dig_bc = [_mk_bc_tables(tabs, s) for s in tabs.digits]
    md_bc = _mk_bc_tables(tabs, tabs.md)
    md = tabs.md

    def cshard(x, *axes):
        if rules is None:
            return x
        from repro.distributed.sharding import sanitize_spec
        return rules.constrain(x, *sanitize_spec(rules, axes, x.shape))

    def fn(c0, c1, u_mont, rk0_mont, rk1_mont):
        c0 = cshard(c0, "ct_batch", "limbs", None)
        c1 = cshard(c1, "ct_batch", "limbs", None)
        # ---- hoist: Decomp + ModUp (BaseConv = the collective stage) ----
        digs = []
        for j, spec in enumerate(tabs.digits):
            own, gen = spec["own_rows"], spec["gen_rows"]
            dig_eval = c1[:, own[0]: own[-1] + 1]
            coeff = ntt.intt_mont(dig_eval, psii_m[own], ninv_m[own],
                                  q32[own], qneg[own])
            ext = _base_conv_mont(coeff, dig_bc[j], fp_dtype)
            ext = cshard(ext, "ct_batch", "limbs", None)
            ext_eval = ntt.ntt_mont(ext, psi_m[gen], q32[gen], qneg[gen])
            x = jnp.zeros((c1.shape[0], M, N), jnp.uint32)
            x = x.at[:, own].set(dig_eval).at[:, gen].set(ext_eval)
            digs.append(x)
        digits = jnp.stack(digs, axis=1)                    # (CTB, β, M, N)
        digits = cshard(digits, "ct_batch", None, "limbs", None)
        zeros_sp = jnp.zeros((c0.shape[0], p.k, N), jnp.uint32)
        c0e = jnp.concatenate(
            [mm.montmul(c0, jnp.asarray(tabs.p_raise_m), q32[: L + 1],
                        qneg[: L + 1]), zeros_sp], axis=1)
        c1e = jnp.concatenate(
            [mm.montmul(c1, jnp.asarray(tabs.p_raise_m), q32[: L + 1],
                        qneg[: L + 1]), zeros_sp], axis=1)

        # ---- rotation loop (fused Automorph→KeyIP→DiagIP, limb-local) ----
        def body(acc, t):
            a0, a1 = acc
            pm = perms[t]
            dig_rot = jnp.take(digits, pm, axis=-1)
            c0r = jnp.take(c0e, pm, axis=-1)
            k0 = jnp.zeros_like(a0)
            k1 = jnp.zeros_like(a1)
            for j in range(nb):
                k0 = mm.montadd(k0, mm.montmul(dig_rot[:, j], rk0_mont[t, j],
                                               q32, qneg), q32)
                k1 = mm.montadd(k1, mm.montmul(dig_rot[:, j], rk1_mont[t, j],
                                               q32, qneg), q32)
            is_id = (t == tabs.d // 2)      # z=0 slot bypasses KeyIP
            t0 = jnp.where(is_id, c0e, mm.montadd(k0, c0r, q32))
            t1 = jnp.where(is_id, c1e, k1)
            a0 = mm.montadd(a0, mm.montmul(u_mont[t], t0, q32, qneg), q32)
            a1 = mm.montadd(a1, mm.montmul(u_mont[t], t1, q32, qneg), q32)
            a0 = cshard(a0, "ct_batch", "limbs", None)
            a1 = cshard(a1, "ct_batch", "limbs", None)
            return (a0, a1), None

        z = jnp.zeros((c0.shape[0], M, N), jnp.uint32)
        # unroll>1 lets XLA fuse several rotations per HBM round-trip of the
        # hoisted digits (the paper's VMEM-residency win, approximated in
        # XLA; the Pallas fused kernel realizes it exactly — §Perf set-c)
        (acc0, acc1), _ = jax.lax.scan(body, (z, z), jnp.arange(tabs.d),
                                       unroll=unroll)

        # ---- merged ModDown+Rescale (second collective stage) ----
        def mod_down(acc):
            drop, out = md["drop_rows"], md["out_rows"]
            xp = ntt.intt_mont(acc[:, drop], psii_m[drop], ninv_m[drop],
                               q32[drop], qneg[drop])
            conv = _base_conv_mont(xp, md_bc, fp_dtype)
            conv_eval = ntt.ntt_mont(conv, psi_m[out], q32[out], qneg[out])
            diff = mm.montsub(acc[:, out], conv_eval, q32[out])
            return mm.montmul(diff, jnp.asarray(md["p_inv_m"]), q32[out],
                              qneg[out])

        return mod_down(acc0), mod_down(acc1)

    return fn


# ---------------------------------------------------------------------------
# schedule="sharded": the shard_map SPMD program behind the compile API
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class ShardTables:
    """Constant tables for the shard_map'd limb-sharded MO-HLT at one
    (params, level, n_model) compile point.

    The extended limb axis (the ``full`` basis, M rows) is padded to
    ``M_pad = rows_loc * n_model`` so the ``model`` mesh axis always divides
    it (the non-divisible-device-count path). Padding rows carry valid moduli
    (copies of the last real row) and all-zero operands, so every stage maps
    them zero -> zero. PURE data — ownership lives in the HEContext operand
    arena (core/compile.py), never in module state.
    """
    params: HEParams
    level: int
    n_model: int
    full: tuple                    # prime indices [Q_level..., P...], len M
    M: int
    M_pad: int
    rows_loc: int                  # M_pad // n_model (rows per model rank)
    # replicated main-basis tables (hoist y-stage; digit own rows are main)
    q_main: np.ndarray             # (level+1, 1) u32
    qneg_main: np.ndarray          # (level+1, 1)
    psii_main: np.ndarray          # (level+1, N) mont
    ninv_main: np.ndarray          # (level+1, 1) mont
    # per-row tables over the padded extended basis (limb-sharded in specs)
    q32: np.ndarray                # (M_pad, 1)
    qneg: np.ndarray               # (M_pad, 1)
    psi_m: np.ndarray              # (M_pad, N) mont twiddles
    psii_m: np.ndarray             # (M_pad, N)
    ninv_m: np.ndarray             # (M_pad, 1) mont
    p_raise_m: np.ndarray          # (M_pad, 1) [P]_{q_i} mont; 0 off-main
    digits: list                   # per digit: dict(sl, hat_inv_m, inv_d,
    #                                W_full, D_full, own_mask)
    md: dict                       # merged ModDown+Rescale tables


def build_shard_tables(params: HEParams, level: int,
                       n_model: int) -> ShardTables:
    """Tables for ``make_sharded_hlt_fn`` — pure, deterministic, arena-owned.

    Digit/ModDown BaseConv tables are expressed over the FULL padded row axis
    (zero off their target rows) so each model rank's row block is a plain
    slice — no per-device index bookkeeping inside the SPMD program.
    """
    ctx = get_context(params)
    tools = RnsTools(ctx)
    N = params.N
    n_model = max(1, int(n_model))
    bases = tools.digit_bases(level)
    full = bases[0][2]
    M = len(full)
    rows_loc = -(-M // n_model)
    M_pad = rows_loc * n_model
    pos = {g: i for i, g in enumerate(full)}

    def pad_rows(x: np.ndarray, copy_last: bool = False) -> np.ndarray:
        if M_pad == M:
            return x
        pad = (np.repeat(x[-1:], M_pad - M, axis=0) if copy_last else
               np.zeros((M_pad - M,) + x.shape[1:], x.dtype))
        return np.concatenate([x, pad], axis=0)

    rows = np.asarray(full)
    qs = np.array([ctx.moduli_host[i] for i in full], np.uint64)[:, None]
    q32 = qs.astype(np.uint32)
    qneg = np.empty((M, 1), np.uint32)
    for r_, i in enumerate(full):
        qneg[r_, 0], _ = mm.mont_constants(ctx.moduli_host[i])
    ninv_m = _mont(np.asarray(ctx.n_inv)[rows].astype(np.uint64), qs)

    nq = level + 1
    Pprod = 1
    for i in range(params.num_main, params.num_total):
        Pprod *= ctx.moduli_host[i]
    p_raise = np.zeros((M, 1), np.uint64)
    p_raise[:nq, 0] = [Pprod % ctx.moduli_host[i] for i in range(nq)]
    p_raise_m = _mont(p_raise, qs)

    digits = []
    for own, gen, _ in bases:
        hat_inv, W, D_mod_t, inv_d = tools._bc_tables(own, gen)
        own_q = np.array([ctx.moduli_host[i] for i in own], np.uint64)[:, None]
        na = len(own)
        W_full = np.zeros((M, na), np.uint64)
        D_full = np.zeros((M, 1), np.uint64)
        gen_rows = np.array([pos[i] for i in gen])
        W_full[gen_rows] = np.asarray(W, np.uint64)        # W is (|gen|, |own|)
        D_full[gen_rows] = np.asarray(D_mod_t, np.uint64)
        own_mask = np.zeros((M, 1), bool)
        own_mask[[pos[i] for i in own]] = True
        digits.append(dict(
            sl=(pos[own[0]], pos[own[-1]] + 1),            # contiguous main rows
            hat_inv_m=_mont(np.asarray(hat_inv, np.uint64), own_q),
            inv_d=np.asarray(inv_d, np.float64),
            W_full=pad_rows(_mont(W_full, qs)),
            D_full=pad_rows(_mont(D_full, qs)),
            own_mask=pad_rows(own_mask),
        ))

    # merged ModDown+Rescale: drop specials + q_level (order must match the
    # single-device oracle: P_ext = specials, then q_level — the f64 overflow
    # count v sums y rows in exactly this order)
    spec = tuple(range(params.num_main, params.num_total))
    P_ext = spec + (level,)
    Q_out = tuple(range(level))
    hat_inv, W, D_mod_t, inv_d = tools._bc_tables(P_ext, Q_out)
    p_inv = tools._moddown_tables(P_ext, Q_out)
    drop_rows = np.array([pos[i] for i in P_ext])
    nd = len(P_ext)
    hat_full = np.zeros((M, 1), np.uint64)
    hat_full[drop_rows] = np.asarray(hat_inv, np.uint64)
    sel_drop = np.zeros((nd, M_pad), np.uint32)
    sel_drop[np.arange(nd), drop_rows] = 1
    W_full = np.zeros((M, nd), np.uint64)
    D_full = np.zeros((M, 1), np.uint64)
    pinv_full = np.zeros((M, 1), np.uint64)
    out_rows = np.array([pos[i] for i in Q_out])
    W_full[out_rows] = np.asarray(W, np.uint64)            # (|Q_out|, |P_ext|)
    D_full[out_rows] = np.asarray(D_mod_t, np.uint64)
    pinv_full[out_rows] = np.asarray(p_inv, np.uint64)
    md = dict(
        n_drop=nd,
        hat_inv_full=pad_rows(_mont(hat_full, qs)),
        sel_drop=sel_drop,
        inv_d=np.asarray(inv_d, np.float64),
        W_full=pad_rows(_mont(W_full, qs)),
        D_full=pad_rows(_mont(D_full, qs)),
        p_inv_full=pad_rows(_mont(pinv_full, qs)),
    )
    return ShardTables(
        params=params, level=level, n_model=n_model, full=full, M=M,
        M_pad=M_pad, rows_loc=rows_loc,
        q_main=q32[:nq], qneg_main=qneg[:nq],
        psii_main=np.asarray(ctx.psi_inv_brv_mont)[rows[:nq]],
        ninv_m=pad_rows(ninv_m, True), ninv_main=ninv_m[:nq],
        q32=pad_rows(q32, True), qneg=pad_rows(qneg, True),
        psi_m=pad_rows(np.asarray(ctx.psi_brv_mont)[rows], True),
        psii_m=pad_rows(np.asarray(ctx.psi_inv_brv_mont)[rows], True),
        p_raise_m=pad_rows(p_raise_m),
        digits=digits, md=md)


#: tab-dict keys whose LEADING axis is the digit index (limb rows on axis 1)
_STACKED_TAB_KEYS = ("w_stack", "d_stack", "mask_stack")


def _tab_keys(tabs: ShardTables) -> list:
    return (["q32", "qneg", "psi_m", "psii_m", "psi_tw", "psii_tw", "ninv_m",
             "p_raise_m",
             "md_hat_inv", "md_W", "md_D", "md_p_inv", "sel_drop"]
            + list(_STACKED_TAB_KEYS)
            + [f"{pre}{j}" for j in range(len(tabs.digits))
               for pre in ("W", "D", "mask")])


def shard_operand_arrays(tabs: ShardTables) -> dict:
    """The limb-sharded table operands passed INTO the shard_map program
    (each model rank receives its row block via the in_specs — nothing is
    dynamically indexed by device id inside the program).

    ``w_stack``/``d_stack``/``mask_stack`` are the per-digit BaseConv tables
    restacked to a leading digit axis (columns zero-padded to the common
    ``alpha``), the layout the fused base-change kernel
    (kernels/basechange.py ``baseconv_ntt``) grids over — the per-digit
    ``W{j}``/``D{j}``/``mask{j}`` keys stay for the XLA stage baseline."""
    alpha = max(dg["W_full"].shape[1] for dg in tabs.digits)
    out = dict(
        q32=tabs.q32, qneg=tabs.qneg, psi_m=tabs.psi_m, psii_m=tabs.psii_m,
        psi_tw=ntt.expand_twiddles(tabs.psi_m),
        psii_tw=ntt.expand_twiddles(tabs.psii_m, inverse=True),
        ninv_m=tabs.ninv_m, p_raise_m=tabs.p_raise_m,
        md_hat_inv=tabs.md["hat_inv_full"], md_W=tabs.md["W_full"],
        md_D=tabs.md["D_full"], md_p_inv=tabs.md["p_inv_full"],
        sel_drop=tabs.md["sel_drop"],
        w_stack=np.stack([
            np.pad(dg["W_full"], ((0, 0), (0, alpha - dg["W_full"].shape[1])))
            for dg in tabs.digits]),
        d_stack=np.stack([dg["D_full"] for dg in tabs.digits]),
        mask_stack=np.stack([dg["own_mask"].astype(np.uint32)
                             for dg in tabs.digits]),
    )
    for j, dg in enumerate(tabs.digits):
        out[f"W{j}"] = dg["W_full"]
        out[f"D{j}"] = dg["D_full"]
        out[f"mask{j}"] = dg["own_mask"]
    return {k: jnp.asarray(v) for k, v in out.items()}


def _physical_axes(rules, logical: str) -> tuple:
    """Mesh axis names a logical axis maps to (empty when unmapped/no mesh)."""
    if rules is None or rules.mesh is None:
        return ()
    axes = rules.rules.get(logical) or ()
    return tuple(a for a in axes if a in rules.mesh.shape)


def build_slot_tables(diag_slots, ct_slots, b_pad: int) -> dict:
    """Pad the batch-index -> operand-slot maps to the ct-axis multiple.

    ``diag_slots``: per-element unique-DiagSet slot (always known at compile
    time).  ``ct_slots``: per-element unique-ciphertext slot — the compile-time
    ALIASING HINT for the in-program hoist dedup (hemm Step-2 passes
    ``(0,)*l + (1,)*l``), or ``None`` when the aliasing is only known at call
    time (core/compile.py then rebuilds the ct table per call from object
    identity).  Padding elements point at slot 0; their outputs are computed
    and dropped by the caller.

    Pure — the result is stored in the owning HEContext's operand arena
    (generation-guarded, dropped on re-keygen) like every other operand.
    """
    B = len(diag_slots)
    assert b_pad >= B, (b_pad, B)
    pad_d = list(diag_slots) + [0] * (b_pad - B)
    out = dict(diag=jnp.asarray(np.array(pad_d, np.int32)))
    if ct_slots is not None:
        assert len(ct_slots) == B, (len(ct_slots), B)
        pad_c = list(ct_slots) + [0] * (b_pad - B)
        out["ct"] = jnp.asarray(np.array(pad_c, np.int32))
    else:
        out["ct"] = None
    return out


def expected_collectives(tabs: ShardTables) -> dict:
    """The sharded program's collective CONTRACT, owned next to the program
    builder and consumed by the verifier (``repro.analysis.jaxpr_lint``,
    rule JX001): the merged ModDown+Rescale BaseConv is the ONLY collective
    — one exact one-contributor-per-row psum per output poly (c0', c1') when
    the limb axis is really sharded, none at all when n_model == 1 (the
    body is then emitted without shard_map/psum), and never any other
    collective primitive."""
    return {"psum": 2 if tabs.n_model > 1 else 0}


def _operand_specs(tabs: ShardTables, limb) -> tuple:
    """PartitionSpecs of the compile-time operands: (table dict, rotation
    operand dict), limb rows over ``limb`` (None = replicated)."""
    from jax.sharding import PartitionSpec as P
    tab_specs = {k: (P(None, limb)
                     if k == "sel_drop" or k in _STACKED_TAB_KEYS
                     else P(limb, None))
                 for k in _tab_keys(tabs)}
    op_specs = dict(
        u=P(None, None, limb, None),
        rk0=P(None, None, None, limb, None),
        rk1=P(None, None, None, limb, None),
        perms=P(None, None, None), is_id=P(None, None, None))
    return tab_specs, op_specs


def place_operands(tabs: ShardTables, rules, tab_arrays: dict,
                   operands: tuple) -> tuple:
    """Put the table arrays and the stacked (u, rk0, rk1, perms, is_id)
    rotation operands on the mesh with the shardings the SPMD program reads
    them with, so each device holds its limb rows once instead of the
    program resharding from one device on every call."""
    from jax.sharding import NamedSharding
    mesh = rules.mesh
    if mesh is None:
        return tab_arrays, operands
    limb_axes = _physical_axes(rules, "limbs") if tabs.n_model > 1 else ()
    tab_specs, op_specs = _operand_specs(tabs, limb_axes or None)
    put = lambda x, spec: jax.device_put(x, NamedSharding(mesh, spec))
    tabs_out = {k: put(v, tab_specs[k]) for k, v in tab_arrays.items()}
    ops_out = tuple(put(x, op_specs[k]) for k, x in zip(
        ("u", "rk0", "rk1", "perms", "is_id"), operands)) if operands else ()
    return tabs_out, ops_out


def make_sharded_hlt_fn(tabs: ShardTables, rules, *, d_pad: int, nbeta: int,
                        fp_dtype=jnp.float64, unroll: int = 1,
                        datapath: str = "pallas", chunk: Optional[int] = None,
                        hoist_layout: str = "dedup", stages: str = "pallas"):
    """Build the ``schedule="sharded"`` SPMD program for one compile point.

    ``stages`` picks the hoist / merged-ModDown STAGE coverage of the
    ``datapath="pallas"`` body (HEContext.datapath threads it through):
    ``"pallas"`` (default) runs the per-rank hoist through the fused
    base-change kernels (kernels/basechange.py — replicated main-basis
    iNTT·q̂⁻¹, then rank-local BaseConv+NTT off the stacked digit tables)
    and splits the merged ModDown into Pallas pre-psum (iNTT·q̂⁻¹ on the
    rank rows) → the sel_drop scatter + psum (STILL the only collective,
    byte-identical traffic) → Pallas post-psum (BaseConv+NTT+sub+·P⁻¹);
    ``"xla"`` keeps both stages on the pre-fusion XLA lowering.  The
    ``datapath="xla"`` baseline body ignores ``stages``.

    Returns ``fn(args) -> (acc0, acc1)``.  With ``datapath="pallas"`` (the
    production default) ``args`` is a dict over H hoist inputs:

    ======== =========================== ====================================
    key      shape                       sharding
    ======== =========================== ====================================
    c0u,c1u  (H, M_pad, N) u32           limbs (hoist inputs, zero-ext. rows)
    c1rep    (H, level+1, N) u32         limb-replicated (hoist input)
    ct_slots (B,) i32                    ct_batch (batch elem -> hoist slot)
    slots    (B,) i32                    ct_batch (batch elem -> diag slot)
    u        (S, d_pad, M_pad, N) u32    limbs (mont diagonals per slot)
    rk0,rk1  (S, d_pad, b, M_pad, N) u32 limbs (mont rotation keys)
    perms    (S, d_pad, N) i32           replicated
    is_id    (S, d_pad, 1) i32           replicated
    tab      shard_operand_arrays(tabs)  limbs (per-row constant tables)
    ======== =========================== ====================================

    Each model rank hoists its hoist inputs and then drives its limb-row
    shard through the fused Automorph→KeyIP→DiagIP Pallas kernel
    (``kernels/fused_hlt.py fused_hlt_indexed``) with the scalar-prefetch
    slot vectors routing each batch element's DMA to its hoisting product /
    diagonal set.  ``chunk`` is the kernel's per-rank rotation chunk (VMEM
    budget pick, must divide ``d_pad``; defaults to ``d_pad``).

    ``hoist_layout`` picks how hoist inputs are laid out across the ct axis
    (the caller — core/compile.py — chooses whichever hoists FEWER
    ciphertexts per rank for the call's aliasing pattern):

    - ``"dedup"`` — H = unique ciphertexts, REPLICATED over the ct axis;
      ``ct_slots`` holds global unique-ct ids.  Every rank hoists each
      unique input once (Step-2's ``[A0]·l + [B0]·l`` batch: 2 hoists per
      rank, not 2·l), at the cost of holding all H on every ct rank.
    - ``"element"`` — H = B_pad per-element inputs SHARDED over the ct axis
      (like the xla baseline); ``ct_slots`` holds rank-LOCAL indices
      (``arange(B_pad) % B_loc``).  Each rank hoists only its local batch
      elements — better than replicating when the batch is mostly distinct.

    With ``datapath="xla"`` (``schedule="sharded_xla"``, the fusion baseline
    kept for benchmarks) ``args`` instead carries per-ELEMENT tensors
    ``c0f,c1f (B, M_pad, N)`` / ``c1rep (B, level+1, N)`` sharded over
    ``ct_batch``, every element re-hoists, and the rotation loop lowers
    through plain XLA (lax.scan).

    B must be a multiple of the ct-axis device count (core/compile.py pads:
    zero ciphertexts on the xla path, slot-0 aliases on the pallas path).
    Outputs are (B, M_pad, N) x2 after the merged ModDown+Rescale; real
    output rows are 0..level-1 (caller slices).

    ModUp is collective-free: the hoist reads the limb-REPLICATED ``c1rep``
    and every model rank materializes only its local digit rows. The merged
    ModDown BaseConv is the ONLY collective — a ``psum`` of the (|drop|, N)
    conversion inputs where each limb row has exactly one contributor, hence
    exact (no float reordering) and bit-identical to the single-device MO
    schedule.
    """
    from jax.sharding import PartitionSpec as P

    assert datapath in ("pallas", "xla"), datapath
    assert stages in ("pallas", "xla"), stages
    mesh = rules.mesh
    limb_axes = _physical_axes(rules, "limbs") if tabs.n_model > 1 else ()
    ct_axes = _physical_axes(rules, "ct_batch")
    limb = limb_axes if limb_axes else None
    ct = ct_axes if ct_axes else None
    kchunk = d_pad if chunk is None else max(1, min(int(chunk), d_pad))
    assert d_pad % kchunk == 0, (d_pad, kchunk)

    q_main = jnp.asarray(tabs.q_main)
    qneg_main = jnp.asarray(tabs.qneg_main)
    psii_main = jnp.asarray(tabs.psii_main)
    ninv_main = jnp.asarray(tabs.ninv_main)
    dig_hat = [jnp.asarray(dg["hat_inv_m"]) for dg in tabs.digits]
    dig_invd = [jnp.asarray(dg["inv_d"].astype(fp_dtype))
                for dg in tabs.digits]
    dig_sl = [dg["sl"] for dg in tabs.digits]
    md_invd = jnp.asarray(tabs.md["inv_d"].astype(fp_dtype))

    def baseconv_rows(y, W_loc, D_loc, inv_d, q, qn):
        """y (B, |S|, N) std-domain -> converted rows (B, rows_loc, N) over
        this rank's row block (W/D are zero off the target rows)."""
        v = jnp.floor(jnp.sum(y.astype(fp_dtype) * inv_d, axis=-2)
                      + 0.5e-6).astype(jnp.uint32)               # (B, N)
        prod = mm.montmul(y[:, None], W_loc[:, :, None],
                          q[:, None], qn[:, None])   # (B, rows, |S|, N)
        acc = _mod_reduce(prod, q[:, None], axis=-2)
        corr = mm.montmul(v[:, None, :], D_loc, q, qn)
        return mm.montsub(acc, corr, q)

    def hoist_local(t, c1rep, c1f, q, qn):
        """Decomp + ModUp of each leading-axis element, collective-free off
        the limb-replicated ``c1rep``; own rows come from the rank's ``c1f``
        shard.  Returns digits (·, β', rows_loc, N)."""
        digs = []
        for j in range(len(dig_sl)):
            s_, e_ = dig_sl[j]
            coeff = ntt.intt_mont(c1rep[:, s_:e_], psii_main[s_:e_],
                                  ninv_main[s_:e_], q_main[s_:e_],
                                  qneg_main[s_:e_])
            y = mm.montmul(coeff, dig_hat[j], q_main[s_:e_], qneg_main[s_:e_])
            ext = baseconv_rows(y, t[f"W{j}"], t[f"D{j}"], dig_invd[j], q, qn)
            ext_eval = ntt.ntt_mont(ext, t["psi_m"], q, qn)
            digs.append(jnp.where(t[f"mask{j}"].astype(bool), c1f, ext_eval))
        return jnp.stack(digs, axis=1)

    def make_mod_down(t, q, qn):
        """Merged ModDown+Rescale: the ONE collective (BaseConv psum)."""
        def mod_down(acc):
            xp = ntt.intt_mont(acc, t["psii_m"], t["ninv_m"], q, qn)
            y = mm.montmul(xp, t["md_hat_inv"], q, qn)   # zero off drop rows
            # scatter local drop rows to their P_ext position, then psum: one
            # contributor per row -> the sum is exact (collective volume is
            # the paper's BaseConv traffic, nothing else crosses ranks)
            part = jnp.sum(t["sel_drop"][None, :, :, None] * y[:, None],
                           axis=2, dtype=jnp.uint32)     # (B, |drop|, N)
            y_drop = (jax.lax.psum(part, limb_axes) if limb_axes else part)
            conv = baseconv_rows(y_drop, t["md_W"], t["md_D"], md_invd, q, qn)
            conv_eval = ntt.ntt_mont(conv, t["psi_m"], q, qn)
            diff = mm.montsub(acc, conv_eval, q)
            return mm.montmul(diff, t["md_p_inv"], q, qn)
        return mod_down

    # ---- fused stage coverage (stages="pallas"): per-rank base-change
    # kernels; same math row-for-row as hoist_local/make_mod_down above ----
    fused_stages = datapath == "pallas" and stages == "pallas"
    if fused_stages:
        from repro.kernels import basechange, ops as _ops
        interp = _ops._interp()
        p = tabs.params
        N = p.N
        nq = tabs.level + 1
        nbeta_t = len(tabs.digits)
        alpha = max(e_ - s_ for s_, e_ in dig_sl)
        R = nbeta_t * alpha
        # replicated digit-padded stage-1 tables (main basis; padded rows
        # carry zero twiddles/scales and map zero -> zero)
        h_psii = np.zeros((R, N), np.uint32)
        h_ninv = np.zeros((R, 1), np.uint32)
        h_hat = np.zeros((R, 1), np.uint32)
        h_q = np.full((R, 1), np.asarray(tabs.q_main)[0, 0], np.uint32)
        h_qneg = np.full((R, 1), np.asarray(tabs.qneg_main)[0, 0], np.uint32)
        h_invd = np.zeros((nbeta_t, alpha, 1), np.float64)
        for j, (s_, e_) in enumerate(dig_sl):
            na = e_ - s_
            rows = slice(j * alpha, j * alpha + na)
            h_psii[rows] = np.asarray(tabs.psii_main)[s_:e_]
            h_ninv[rows] = np.asarray(tabs.ninv_main)[s_:e_]
            h_q[rows] = np.asarray(tabs.q_main)[s_:e_]
            h_qneg[rows] = np.asarray(tabs.qneg_main)[s_:e_]
            h_hat[rows] = np.asarray(tabs.digits[j]["hat_inv_m"])
            h_invd[j, :na] = tabs.digits[j]["inv_d"]
        h_psii = jnp.asarray(ntt.expand_twiddles(h_psii, inverse=True))
        h_ninv, h_hat = jnp.asarray(h_ninv), jnp.asarray(h_hat)
        h_q, h_qneg = jnp.asarray(h_q), jnp.asarray(h_qneg)
        h_invd = jnp.asarray(h_invd.astype(fp_dtype))

    def hoist_local_fused(t, c1rep, c1f, q, qn):
        """Fused hoist_local: stage 1 on the replicated main rows, stage 2
        (BaseConv + NTT + own-row passthrough) on this rank's row block."""
        def one(c1r_i, c1f_i):
            x_dig = jnp.pad(c1r_i, ((0, R - nq), (0, 0)))
            y = basechange.intt_scale(x_dig, h_psii, h_ninv, h_hat, h_q,
                                      h_qneg, interpret=interp)
            return basechange.baseconv_ntt(
                y, t["w_stack"], t["d_stack"], h_invd, t["psi_tw"], q, qn,
                c1f_i, t["mask_stack"], interpret=interp)
        return jax.vmap(one)(c1rep, c1f)

    def make_mod_down_fused(t, q, qn):
        """Fused merged ModDown+Rescale — the sel_drop scatter and the psum
        (STILL the only collective) stay on XLA between the two kernels."""
        def mod_down(acc):
            y = jax.vmap(lambda x: basechange.intt_scale(
                x, t["psii_tw"], t["ninv_m"], t["md_hat_inv"], q, qn,
                interpret=interp))(acc)
            part = jnp.sum(t["sel_drop"][None, :, :, None] * y[:, None],
                           axis=2, dtype=jnp.uint32)     # (B, |drop|, N)
            y_drop = (jax.lax.psum(part, limb_axes) if limb_axes else part)
            return jax.vmap(lambda x, yd: basechange.moddown_finish(
                x, yd, t["md_W"], t["md_D"], md_invd, t["psi_tw"],
                t["md_p_inv"], q, qn, interpret=interp))(acc, y_drop)
        return mod_down

    def body_pallas(a):
        """Fused datapath: deduped hoist + per-rank fused_hlt_indexed."""
        from repro.kernels import ops
        t = a["tab"]
        q, qn = t["q32"], t["qneg"]
        # ---- hoist H UNIQUE cts (ct-slot dedup), limb-local rows ----
        digits = (hoist_local_fused if fused_stages else hoist_local)(
            t, a["c1rep"], a["c1u"], q, qn)
        c0e = mm.montmul(a["c0u"], t["p_raise_m"], q, qn)
        c1e = mm.montmul(a["c1u"], t["p_raise_m"], q, qn)
        # ---- fused rotation loop on this rank's limb-row shard ----
        # (a key copy per diagonal, read as a table of S·d_pad keys)
        S, d, nb, m_loc, n = a["rk0"].shape
        acc0, acc1 = ops.fused_hlt_indexed(
            digits, c0e, c1e, a["u"], a["rk0"].reshape(S * d, nb, m_loc, n),
            a["rk1"].reshape(S * d, nb, m_loc, n),
            jnp.arange(S * d, dtype=jnp.int32).reshape(S, d),
            jnp.arange(m_loc, dtype=jnp.int32), a["perms"], a["is_id"],
            a["ct_slots"], a["slots"], q, qn, chunk=kchunk)
        mod_down = (make_mod_down_fused if fused_stages
                    else make_mod_down)(t, q, qn)
        return mod_down(acc0), mod_down(acc1)

    def body_xla(a):
        """Fusion baseline: per-element hoist + XLA-lowered rotation scan."""
        t = a["tab"]
        q, qn = t["q32"], t["qneg"]

        # ---- hoist: Decomp + ModUp, once per batch ELEMENT (no dedup) ----
        digits = hoist_local(t, a["c1rep"], a["c1f"], q, qn)
        c0e = mm.montmul(a["c0f"], t["p_raise_m"], q, qn)
        c1e = mm.montmul(a["c1f"], t["p_raise_m"], q, qn)

        # ---- rotation loop (Automorph->KeyIP->DiagIP, limb-local) ----
        slots = a["slots"]
        perms, is_id = a["perms"], a["is_id"]
        u, rk0, rk1 = a["u"], a["rk0"], a["rk1"]

        def rot_body(carry, ti):
            a0, a1 = carry
            pm = perms[slots, ti]                              # (B, N)
            dig_rot = jnp.take_along_axis(
                digits, pm[:, None, None, :], axis=-1)
            c0r = jnp.take_along_axis(c0e, pm[:, None, :], axis=-1)
            u_t = u[slots, ti]                                 # (B, rows, N)
            k0w, k1w = rk0[slots, ti], rk1[slots, ti]
            k0 = jnp.zeros_like(a0)
            k1 = jnp.zeros_like(a1)
            for j in range(nbeta):
                k0 = mm.montadd(k0, mm.montmul(dig_rot[:, j], k0w[:, j],
                                               q, qn), q)
                k1 = mm.montadd(k1, mm.montmul(dig_rot[:, j], k1w[:, j],
                                               q, qn), q)
            sel = is_id[slots, ti].astype(bool)[:, :, None]    # (B, 1, 1)
            t0 = jnp.where(sel, c0e, mm.montadd(k0, c0r, q))
            t1 = jnp.where(sel, c1e, k1)
            a0 = mm.montadd(a0, mm.montmul(u_t, t0, q, qn), q)
            a1 = mm.montadd(a1, mm.montmul(u_t, t1, q, qn), q)
            return (a0, a1), None

        z = jnp.zeros(c0e.shape, jnp.uint32)
        (acc0, acc1), _ = jax.lax.scan(rot_body, (z, z),
                                       jnp.arange(d_pad), unroll=unroll)
        mod_down = make_mod_down(t, q, qn)
        return mod_down(acc0), mod_down(acc1)

    tab_specs, op_specs = _operand_specs(tabs, limb)
    if datapath == "pallas":
        assert hoist_layout in ("dedup", "element"), hoist_layout
        body = body_pallas
        ct_h = None if hoist_layout == "dedup" else ct
        in_specs = (dict(
            c0u=P(ct_h, limb, None), c1u=P(ct_h, limb, None),
            c1rep=P(ct_h, None, None),
            ct_slots=P(ct), slots=P(ct),
            tab=tab_specs, **op_specs),)
    else:
        body = body_xla
        in_specs = (dict(
            c0f=P(ct, limb, None), c1f=P(ct, limb, None),
            c1rep=P(ct, None, None), slots=P(ct),
            tab=tab_specs, **op_specs),)
    out_specs = (P(ct, limb, None),) * 2
    if mesh is None:
        return body
    return jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def lower_mo_hlt_spmd(params: HEParams, mesh, rules, d: int = 127,
                      ctb: Optional[int] = None, unroll: int = 1):
    """Lower the SPMD MO-HLT for the dry-run (ShapeDtypeStructs only)."""
    if ctb is None:
        ctb = int(np.prod([mesh.shape[a] for a in mesh.axis_names
                           if a in ("pod", "data")]))
    tabs = build_tables(params, d, ctb)
    fn = make_mo_hlt_fn(tabs, rules, unroll=unroll)
    L, N, M = params.L, params.N, len(tabs.full)
    nb = len(tabs.digits)
    u32 = jnp.uint32
    sds = jax.ShapeDtypeStruct
    args = (sds((ctb, L + 1, N), u32), sds((ctb, L + 1, N), u32),
            sds((d, M, N), u32), sds((d, nb, M, N), u32),
            sds((d, nb, M, N), u32))
    from repro.distributed.sharding import sanitize_spec

    def sh(axes, shape):
        return rules.sharding(*sanitize_spec(rules, axes, shape))
    in_sh = tuple(sh(ax, a.shape) for ax, a in zip(
        [("ct_batch", "limbs", None), ("ct_batch", "limbs", None),
         (None, "limbs", None), (None, None, "limbs", None),
         (None, None, "limbs", None)], args, strict=True))
    out_shape = (ctb, L, N)
    out_sh = (sh(("ct_batch", "limbs", None), out_shape),) * 2
    return jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh).lower(*args)
