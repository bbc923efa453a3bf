"""Fused base-change pipelines — Pallas TPU kernels.

The two remaining XLA-lowered stages of the HLT pipeline are the hoist
(Decomp → iNTT → ModUp-BaseConv → NTT) and the merged ModDown+Rescale
(iNTT → BaseConv → NTT → sub → ·P⁻¹). Both are the same shape of
computation — a per-row inverse transform, a small limb-axis matmul
(BaseConv), and a per-row forward transform — so they share two row-wise
kernels here:

* ``intt_scale`` — grid over rows: one resident iNTT pass (all log2(N)
  butterfly stages, core/ntt.py ``intt_tile``) followed by a
  montmul with a per-row scale (``q̂_i⁻¹`` for the hoist digits, the
  ModDown drop-basis ``q̂_i⁻¹`` otherwise).
* ``baseconv_ntt`` / ``moddown_finish`` — grid over *target* rows: the
  HPS BaseConv as a MAC over the source rows (f32/f64 floor-correction
  in-tile), then one resident forward-NTT pass, then
  either the hoist's own-row passthrough select or ModDown's
  ``(x - conv)·P⁻¹``.

Everything stays on the u32 Montgomery datapath; with f64 correction
tables (the CPU) it is bit-exact vs the u64 reference schedules
(tests/test_fused_datapath.py), with the chip's f32 tables the overflow
count may be off by one near an integer (DESIGN.md §7). Table layouts are
digit-padded to ``alpha = max |digit|`` rows so BlockSpec indexing stays
static: padded rows carry zero ``hat_inv``/``inv_d``/``W`` and contribute
exactly zero.

``hoist_db`` is the double-buffered batched hoist: grid over ciphertexts,
input in ANY/HBM memory space, a 2-slot VMEM scratch + DMA semaphore pair
so ciphertext i+1's copy-in overlaps ciphertext i's transform.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import modmath as mm
from repro.core import ntt as core_ntt
from repro.kernels import common

#: floor-correction epsilon of the HPS BaseConv — matches the sharded
#: datapath (core/hlt_dist.py); bit-equal to the u64 reference's f64+1e-9
#: on the verify sets (proven by the parity tests).
CORRECTION_EPS = 0.5e-6


# ---------------------------------------------------------------------------
# row-wise kernels (one (R, C) coefficient tile per row, kernels/common.py)
# ---------------------------------------------------------------------------


def _conv_tile(ys, invd, w, dmod, q, qn):
    """HPS BaseConv of one target row: ys are the source rows' scaled
    coefficient tiles, invd/w their per-row float 1/q_i and Montgomery
    weights (scalars), dmod the target's D mod q (Montgomery)."""
    fsum = None
    for y, c in zip(ys, invd, strict=True):
        term = common.i32_to_float(y, c.dtype) * c
        fsum = term if fsum is None else fsum + term
    v = common.float_to_u32(jnp.floor(fsum + CORRECTION_EPS))
    acc = None
    for y, wa in zip(ys, w, strict=True):
        term = mm.montmul(y, wa, q, qn)
        acc = term if acc is None else mm.montadd(acc, term, q)
    return mm.montsub(acc, mm.montmul(v, dmod, q, qn), q)


def _intt_scale_kernel(x_ref, tw_ref, ninv_ref, scale_ref, q_ref, qneg_ref,
                       o_ref):
    r = pl.program_id(0)
    q, qn = q_ref[r, 0], qneg_ref[r, 0]
    coeff = core_ntt.intt_tile(x_ref[...], tw_ref[...], ninv_ref[r, 0], q, qn)
    o_ref[...] = mm.montmul(coeff, scale_ref[r, 0], q, qn)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intt_scale(x, psii_tw, ninv_m, scale_m, q32, qneg, *, interpret: bool):
    """Per-row iNTT + montmul by a per-row Montgomery scale.

    x: (R, N) eval-domain u32; psii_tw: (R, log2 N, Rt, C) inverse twiddle
    tiles (core/ntt.expand_twiddles); ninv_m/scale_m/q32/qneg: (R, 1).
    Returns (R, N) coeff-domain, scaled."""
    R, N = x.shape
    Rt, C = core_ntt.tile_shape(N)
    row = pl.BlockSpec((None, Rt, C), lambda r: (r, 0, 0))
    tw = pl.BlockSpec((None,) + psii_tw.shape[1:], lambda r: (r, 0, 0, 0))
    with common.lowering_scope(interpret):
        out = pl.pallas_call(
            _intt_scale_kernel,
            grid=(R,),
            in_specs=[row, tw] + [common.SMEM] * 4,
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct((R, Rt, C), jnp.uint32),
            interpret=interpret,
        )(common.tiles(x), psii_tw, ninv_m, scale_m, q32, qneg)
    return common.untiles(out)


def _baseconv_ntt_kernel(y_ref, w_ref, d_ref, invd_ref, tw_ref, q_ref,
                         qneg_ref, pt_ref, mask_ref, o_ref):
    j, m = pl.program_id(0), pl.program_id(1)
    alpha = y_ref.shape[0]
    q, qn = q_ref[m, 0], qneg_ref[m, 0]
    conv = _conv_tile([y_ref[a] for a in range(alpha)],
                      [invd_ref[j, a, 0] for a in range(alpha)],
                      [w_ref[j, m, a] for a in range(alpha)],
                      d_ref[j, m, 0], q, qn)
    res = core_ntt.ntt_tile(conv, tw_ref[...], q, qn)
    o_ref[...] = jnp.where(mask_ref[j, m, 0] != 0, pt_ref[...], res)


@functools.partial(jax.jit, static_argnames=("interpret",))
def baseconv_ntt(y, w, d, inv_d, psi_tw, q32, qneg, passthrough, mask, *,
                 interpret: bool):
    """Fused ModUp-BaseConv + forward NTT + own-row passthrough (the hoist).

    y: (nbeta*alpha, N) scaled digit coeffs (digit j at row block j);
    w: (nbeta, M, alpha) mont; d: (nbeta, M, 1) mont; inv_d: (nbeta,
    alpha, 1) float; psi_tw: (M, log2 N, Rt, C) twiddle tiles; q32/qneg:
    (M, 1); passthrough: (M, N) eval-domain c1 rows (selected where
    mask != 0). Returns digits (nbeta, M, N) in eval domain."""
    nbeta, M, alpha = w.shape
    N = y.shape[-1]
    Rt, C = core_ntt.tile_shape(N)
    ydig = pl.BlockSpec((alpha, Rt, C), lambda j, _m: (j, 0, 0))
    tw = pl.BlockSpec((None,) + psi_tw.shape[1:], lambda _j, m: (m, 0, 0, 0))
    trow = pl.BlockSpec((None, Rt, C), lambda _j, m: (m, 0, 0))
    out = pl.BlockSpec((None, None, Rt, C), lambda j, m: (j, m, 0, 0))
    smem = common.SMEM
    with common.lowering_scope(interpret):
        res = pl.pallas_call(
            _baseconv_ntt_kernel,
            grid=(nbeta, M),
            in_specs=[ydig, smem, smem, smem, tw, smem, smem, trow, smem],
            out_specs=out,
            out_shape=jax.ShapeDtypeStruct((nbeta, M, Rt, C), jnp.uint32),
            interpret=interpret,
        )(common.tiles(y), w, d, inv_d, psi_tw, q32, qneg,
          common.tiles(passthrough), mask)
    return common.untiles(res)


def _moddown_finish_kernel(x_ref, y_ref, w_ref, d_ref, invd_ref, tw_ref,
                           pinv_ref, q_ref, qneg_ref, o_ref):
    r = pl.program_id(0)
    nd = y_ref.shape[0]
    q, qn = q_ref[r, 0], qneg_ref[r, 0]
    conv = _conv_tile([y_ref[a] for a in range(nd)],
                      [invd_ref[a, 0] for a in range(nd)],
                      [w_ref[r, a] for a in range(nd)],
                      d_ref[r, 0], q, qn)
    conv_eval = core_ntt.ntt_tile(conv, tw_ref[...], q, qn)
    diff = mm.montsub(x_ref[...], conv_eval, q)
    o_ref[...] = mm.montmul(diff, pinv_ref[r, 0], q, qn)


@functools.partial(jax.jit, static_argnames=("interpret",))
def moddown_finish(x, y_drop, w, d, inv_d, psi_tw, p_inv_m, q32, qneg, *,
                   interpret: bool):
    """Fused ModDown tail: BaseConv from the drop basis + NTT + sub + ·P⁻¹.

    x: (R, N) eval-domain target rows; y_drop: (nd, N) scaled drop-basis
    coeffs; w: (R, nd) mont; d/p_inv_m/q32/qneg: (R, 1); inv_d: (nd, 1)
    float; psi_tw: (R, log2 N, Rt, C) twiddle tiles. Returns (R, N)
    eval-domain ModDown output."""
    R, N = x.shape
    nd = y_drop.shape[0]
    Rt, C = core_ntt.tile_shape(N)
    row = pl.BlockSpec((None, Rt, C), lambda r: (r, 0, 0))
    full = pl.BlockSpec((nd, Rt, C), lambda _r: (0, 0, 0))
    tw = pl.BlockSpec((None,) + psi_tw.shape[1:], lambda r: (r, 0, 0, 0))
    smem = common.SMEM
    with common.lowering_scope(interpret):
        out = pl.pallas_call(
            _moddown_finish_kernel,
            grid=(R,),
            in_specs=[row, full, smem, smem, smem, tw, smem, smem, smem],
            out_specs=row,
            out_shape=jax.ShapeDtypeStruct((R, Rt, C), jnp.uint32),
            interpret=interpret,
        )(common.tiles(x), common.tiles(y_drop), w, d, inv_d, psi_tw, p_inv_m,
          q32, qneg)
    return common.untiles(out)


# ---------------------------------------------------------------------------
# double-buffered batched hoist
# ---------------------------------------------------------------------------


def _hoist_db_kernel(x_hbm, psii_ref, ninv_ref, hat_ref, qp_ref, qnp_ref,
                     w_ref, d_ref, invd_ref, psi_ref, qf_ref, qnf_ref,
                     mask_ref, o_ref, scratch, sem, *, nbeta: int,
                     alpha: int):
    # `scratch`/`sem` come from scratch_shapes, NOT run_scoped: they must
    # persist across grid steps so the copy started at step b-1 is the one
    # step b waits on (run_scoped re-allocates per step and loses it).
    b = pl.program_id(0)
    nb = pl.num_programs(0)
    R = nbeta * alpha
    M = psi_ref.shape[0]

    # warm-up: ct 0's copy is started (and awaited) by step 0 itself;
    # ct b>0's copy was started by step b-1, so the wait below overlaps
    # it with step b-1's transform.
    @pl.when(b == 0)
    def _():
        pltpu.make_async_copy(x_hbm.at[0], scratch.at[0], sem.at[0]).start()

    slot = b % 2
    pltpu.make_async_copy(x_hbm.at[b], scratch.at[slot], sem.at[slot]).wait()

    @pl.when(b + 1 < nb)
    def _():
        pltpu.make_async_copy(x_hbm.at[b + 1], scratch.at[1 - slot],
                              sem.at[1 - slot]).start()

    x = scratch.at[slot]                                # (R + M, Rt, C)
    ys = []
    for r in range(R):
        qp, qnp = qp_ref[r, 0], qnp_ref[r, 0]
        coeff = core_ntt.intt_tile(x[r], psii_ref[r], ninv_ref[r, 0], qp, qnp)
        ys.append(mm.montmul(coeff, hat_ref[r, 0], qp, qnp))
    for j in range(nbeta):
        yj = ys[j * alpha:(j + 1) * alpha]

        def target_row(m, carry, j=j, yj=yj):
            q, qn = qf_ref[m, 0], qnf_ref[m, 0]
            conv = _conv_tile(yj, [invd_ref[j, a, 0] for a in range(alpha)],
                              [w_ref[j, m, a] for a in range(alpha)],
                              d_ref[j, m, 0], q, qn)
            res = core_ntt.ntt_tile(conv, psi_ref[m], q, qn)
            o_ref[j, m] = jnp.where(mask_ref[j, m, 0] != 0, x[R + m], res)
            return carry

        jax.lax.fori_loop(0, M, target_row, 0)


@functools.partial(jax.jit,
                   static_argnames=("nbeta", "alpha", "interpret"))
def hoist_db(xcat, psii_tw, ninv_m, hat_m, q_pad, qneg_pad, w, d, inv_d,
             psi_tw, q_full, qneg_full, mask, *, nbeta: int, alpha: int,
             interpret: bool):
    """Double-buffered batched hoist: grid over ciphertexts, 2-slot VMEM
    scratch so hoist(i+1)'s DMA overlaps transform(i).

    xcat: (B, nbeta*alpha + M, N) — per ct, the digit-padded c1 rows
    concatenated with the full-basis-padded c1 rows (passthrough source).
    Returns digits (B, nbeta, M, N)."""
    B, rows, N = xcat.shape
    M = psi_tw.shape[0]
    Rt, C = core_ntt.tile_shape(N)
    whole = lambda a: pl.BlockSpec(a.shape, lambda _b: (0,) * a.ndim)
    smem = common.SMEM
    with common.lowering_scope(interpret):
        out = pl.pallas_call(
            functools.partial(_hoist_db_kernel, nbeta=nbeta, alpha=alpha),
            grid=(B,),
            in_specs=[pl.BlockSpec(memory_space=pl.ANY), whole(psii_tw),
                      smem, smem, smem, smem, smem, smem, smem,
                      whole(psi_tw), smem, smem, smem],
            out_specs=pl.BlockSpec((None, nbeta, M, Rt, C),
                                   lambda b: (b, 0, 0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((B, nbeta, M, Rt, C), jnp.uint32),
            scratch_shapes=[
                pltpu.VMEM((2, rows, Rt, C), jnp.uint32),
                pltpu.SemaphoreType.DMA((2,)),
            ],
            interpret=interpret,
        )(common.tiles(xcat), psii_tw, ninv_m, hat_m, q_pad, qneg_pad, w, d,
          inv_d, psi_tw, q_full, qneg_full, mask)
    return common.untiles(out)


# ---------------------------------------------------------------------------
# table builders (host numpy; cached by the engine per level)
# ---------------------------------------------------------------------------


def _mont_col(x_u64, qs_u64):
    return mm.to_mont_host_arr(np.asarray(x_u64, np.uint64),
                               np.asarray(qs_u64, np.uint64))


def build_hoist_tables(ctx, tools, level: int, fp_dtype=np.float64) -> dict:
    """Digit-padded fused-hoist tables at `level` (see module docstring).

    Padded rows (last digit short of alpha) carry zeroed hat_inv / inv_d /
    W columns, so they contribute exactly zero to the BaseConv."""
    p = ctx.params
    bases = tools.digit_bases(level)
    full = bases[0][2]
    pos = {g: i for i, g in enumerate(full)}
    nbeta, alpha = len(bases), max(len(own) for (own, _, _) in bases)
    M, N = len(full), p.N
    qs = np.asarray([ctx.moduli_host[i] for i in range(p.num_total)],
                    np.uint64)
    psii_np = np.asarray(ctx.psi_inv_brv_mont)
    psi_np = np.asarray(ctx.psi_brv_mont)
    ninv_np = np.asarray(ctx.n_inv_mont)[:, 0]
    q32_np = np.asarray(ctx.moduli_u32)[:, 0]
    qneg_np = np.asarray(ctx.qneg_inv)[:, 0]

    R = nbeta * alpha
    psii_pad = np.zeros((R, N), np.uint32)
    ninv_pad = np.zeros((R, 1), np.uint32)
    q_pad = np.ones((R, 1), np.uint32) * q32_np[0]
    qneg_pad = np.ones((R, 1), np.uint32) * qneg_np[0]
    hat_pad = np.zeros((R, 1), np.uint32)
    w = np.zeros((nbeta, M, alpha), np.uint32)
    dmod = np.zeros((nbeta, M, 1), np.uint32)
    inv_d = np.zeros((nbeta, alpha, 1), fp_dtype)
    mask = np.zeros((nbeta, M, 1), np.uint32)

    for j, (own, gen, _) in enumerate(bases):
        hat_inv, W, D_mod_t, invd = tools._bc_tables(own, gen)
        na = len(own)
        rows = slice(j * alpha, j * alpha + na)
        psii_pad[rows] = psii_np[list(own)]
        ninv_pad[rows, 0] = ninv_np[list(own)]
        q_pad[rows, 0] = q32_np[list(own)]
        qneg_pad[rows, 0] = qneg_np[list(own)]
        hat_pad[rows] = _mont_col(hat_inv, qs[list(own)][:, None])
        inv_d[j, :na] = invd.astype(fp_dtype)
        for ti, g in enumerate(gen):
            w[j, pos[g], :na] = _mont_col(W[ti], qs[g])
            dmod[j, pos[g], 0] = _mont_col(D_mod_t[ti], qs[g])[0]
        for g in own:
            mask[j, pos[g], 0] = 1

    rows_full = list(full)
    return dict(
        nbeta=nbeta, alpha=alpha, nq=level + 1,
        psii_tw=jnp.asarray(core_ntt.expand_twiddles(psii_pad, inverse=True)),
        ninv_pad=jnp.asarray(ninv_pad),
        q_pad=jnp.asarray(q_pad), qneg_pad=jnp.asarray(qneg_pad),
        hat_pad=jnp.asarray(hat_pad), w=jnp.asarray(w),
        d=jnp.asarray(dmod), inv_d=jnp.asarray(inv_d),
        psi_tw=jnp.asarray(core_ntt.expand_twiddles(psi_np[rows_full])),
        q_full=jnp.asarray(q32_np[rows_full][:, None]),
        qneg_full=jnp.asarray(qneg_np[rows_full][:, None]),
        mask=jnp.asarray(mask),
    )


def build_moddown_tables(ctx, tools, level: int,
                         fp_dtype=np.float64) -> dict:
    """Merged ModDown+Rescale tables at `level` (drop basis P ∪ {q_ℓ})."""
    p = ctx.params
    nq = level + 1
    spec = tuple(range(p.num_main, p.num_total))
    P = spec + (level,)
    Q = tuple(range(level))
    # extended-layout row indices of the drop basis, in P's order
    drop_idx = np.asarray(list(range(nq, nq + p.k)) + [level], np.int64)
    hat_inv, W, D_mod_t, invd = tools._bc_tables(P, Q)
    p_inv = tools._moddown_tables(P, Q)
    qs = np.asarray([ctx.moduli_host[i] for i in range(p.num_total)],
                    np.uint64)
    psii_np = np.asarray(ctx.psi_inv_brv_mont)
    psi_np = np.asarray(ctx.psi_brv_mont)
    ninv_np = np.asarray(ctx.n_inv_mont)[:, 0]
    q32_np = np.asarray(ctx.moduli_u32)[:, 0]
    qneg_np = np.asarray(ctx.qneg_inv)[:, 0]

    rows_p, rows_q = list(P), list(Q)
    return dict(
        drop_idx=drop_idx, n_out=len(Q),
        psii_tw=jnp.asarray(core_ntt.expand_twiddles(psii_np[rows_p],
                                                     inverse=True)),
        ninv_drop=jnp.asarray(ninv_np[rows_p][:, None]),
        q_drop=jnp.asarray(q32_np[rows_p][:, None]),
        qneg_drop=jnp.asarray(qneg_np[rows_p][:, None]),
        hat_drop=jnp.asarray(_mont_col(hat_inv, qs[rows_p][:, None])),
        w=jnp.asarray(_mont_col(W, qs[rows_q][:, None])),
        d=jnp.asarray(_mont_col(D_mod_t, qs[rows_q][:, None])),
        inv_d=jnp.asarray(invd.astype(fp_dtype)),
        psi_tw=jnp.asarray(core_ntt.expand_twiddles(psi_np[rows_q])),
        q_out=jnp.asarray(q32_np[rows_q][:, None]),
        qneg_out=jnp.asarray(qneg_np[rows_q][:, None]),
        p_inv=jnp.asarray(_mont_col(p_inv[:, 0], qs[rows_q])[:, None]),
    )


# ---------------------------------------------------------------------------
# high-level fused pipelines (single ciphertext; vmap for batches)
# ---------------------------------------------------------------------------


def hoist_fused(c1, t: dict, *, interpret: bool):
    """Fused Decomp→iNTT→ModUp-BaseConv→NTT: c1 (nq, N) eval-domain main
    limbs -> digits (nbeta, M, N) eval-domain (own rows passed through)."""
    nq = c1.shape[0]
    R = t["psii_tw"].shape[0]
    M = t["psi_tw"].shape[0]
    x_dig = jnp.pad(c1, ((0, R - nq), (0, 0)))
    y = intt_scale(x_dig, t["psii_tw"], t["ninv_pad"], t["hat_pad"],
                   t["q_pad"], t["qneg_pad"], interpret=interpret)
    c1f = jnp.pad(c1, ((0, M - nq), (0, 0)))
    return baseconv_ntt(y, t["w"], t["d"], t["inv_d"], t["psi_tw"],
                        t["q_full"], t["qneg_full"], c1f, t["mask"],
                        interpret=interpret)


def hoist_fused_db(c1s, t: dict, *, interpret: bool):
    """Double-buffered batched fused hoist: c1s (B, nq, N) -> (B, nbeta,
    M, N). Same math as vmap(hoist_fused); the DMA of ct i+1 overlaps the
    transform of ct i."""
    B, nq, _N = c1s.shape
    R = t["psii_tw"].shape[0]
    M = t["psi_tw"].shape[0]
    xcat = jnp.concatenate(
        [jnp.pad(c1s, ((0, 0), (0, R - nq), (0, 0))),
         jnp.pad(c1s, ((0, 0), (0, M - nq), (0, 0)))], axis=1)
    return hoist_db(xcat, t["psii_tw"], t["ninv_pad"], t["hat_pad"],
                    t["q_pad"], t["qneg_pad"], t["w"], t["d"], t["inv_d"],
                    t["psi_tw"], t["q_full"], t["qneg_full"], t["mask"],
                    nbeta=t["nbeta"], alpha=t["alpha"], interpret=interpret)


def moddown_fused(x_full, t: dict, *, interpret: bool):
    """Fused merged ModDown+Rescale: x_full (nq+k, N) eval-domain extended
    limbs at level ℓ -> (ℓ, N) eval-domain over Q_{ℓ-1}."""
    x_drop = x_full[t["drop_idx"]]
    y = intt_scale(x_drop, t["psii_tw"], t["ninv_drop"], t["hat_drop"],
                   t["q_drop"], t["qneg_drop"], interpret=interpret)
    n_out = t["n_out"]
    return moddown_finish(x_full[:n_out], y, t["w"], t["d"], t["inv_d"],
                          t["psi_tw"], t["p_inv"], t["q_out"],
                          t["qneg_out"], interpret=interpret)


# ---------------------------------------------------------------------------
# VMEM footprints (rows of N u32 lanes; see costmodel.fused_working_set_bytes)
# ---------------------------------------------------------------------------


def hoist_working_set_rows(nbeta: int, alpha: int, logn: int) -> int:
    """Peak per-grid-step resident rows of the fused hoist (stage 2
    dominates): the digit's alpha scaled rows + out/passthrough rows + the
    target row's log2(N) twiddle rows."""
    return alpha + 2 + logn


def hoist_db_working_set_rows(nbeta: int, alpha: int, m_ext: int,
                              logn: int) -> int:
    """Resident rows of the double-buffered hoist: 2-slot ct scratch +
    every row's log2(N) twiddle rows + one ct's digit output."""
    rows = nbeta * alpha + m_ext
    return 2 * rows + rows * logn + nbeta * m_ext


def moddown_working_set_rows(nd: int, logn: int) -> int:
    """Peak per-grid-step resident rows of the fused ModDown tail: the
    nd drop-basis rows (resident across the output grid) + x/out + the
    target row's log2(N) twiddle rows."""
    return nd + 2 + logn
