"""Fused MO-HLT inner datapath — the paper's key kernel, as Pallas TPU.

One grid step = one (ciphertext × limb × rotation-chunk) tile of the
limb-outer / rotation-inner loop (Fig. 2(B)): a chunk of rotations flows
through KeyIP (β Montgomery MACs against the rot-key rows) → DiagIP
(× plaintext diagonal, accumulate) on one limb. The output block is revisited
across the rotation grid dimension (TPU grid is sequential) — initialized at
rot-step 0, accumulated after — so the accumulator never leaves VMEM.

Automorph runs in XLA just before the kernel (``automorph_operands``): the
chip's kernel compiler has no lane gather by a dynamic index row, so each
batch element's hoisted digits and P·c0 are permuted once per rotation into
HBM and the kernel streams the rotated rows. That costs, per (element,
rotation), one extra HBM write and read of (β+1)·M·N u32 over the in-VMEM
gather of the paper's PE; in exchange every kernel operand is a plain
per-limb coefficient tile.

``fused_hlt_indexed`` runs a batch over DEDUPED operand slots: P·c0/P·c1
are stored once per UNIQUE hoisting product, the diagonals once per UNIQUE
diagonal set, and the rotation keys once in all, as one table that every
launch reads by row. Scalar-prefetch index vectors (ct_slots, diag_slots,
the slot -> key-row table and the limb -> key-row map) drive the BlockSpec
index maps (pltpu.PrefetchScalarGridSpec), so batch element b DMAs the
diagonal tiles of slot diag_slots[b] and each rotation's key rows straight
from the stored arrays — hemm Step-2 runs 2·l HLTs off 2·l stored diagonal
sets and the context's one key table. ``fused_hlt`` (one ciphertext) and
``fused_hlt_batched`` (per-element operands, a key per diagonal) are the
same kernel with trivial slot maps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import modmath as mm
from repro.core import ntt as core_ntt
from repro.kernels import common


def working_set_rows(nbeta: int, chunk: int) -> int:
    """Rows of N u32 coefficients in VMEM per grid step: P·c0, P·c1 and the
    two accumulator rows stay put, and each of the ``chunk`` rotations streams
    its β rotated digit rows, its rotated P·c0 row, one diagonal row and 2β
    rot-key rows. The Pallas pipeline double-buffers every block, hence 2×.

    The single source of truth for the VMEM budget: ``core/costmodel.py``
    ``pick_rotation_chunk`` inverts it to choose ``chunk`` and the verifier
    (``repro.analysis.vmem``, rule VM001) evaluates it forward to reject an
    explicit ``rotation_chunk`` that cannot fit.
    """
    return 2 * (4 + chunk * (3 * nbeta + 2))


def automorph_operands(digits, c0e, perms, ct_slots, diag_slots):
    """Automorph in XLA: batch element b's hoisted digits and P·c0 (slot
    ct_slots[b]) permuted by each rotation of diagonal set diag_slots[b].

    digits: (H, β, M, N); c0e: (H, M, N); perms: (S, d, N) i32.
    Returns (B, d, β+1, M, R, C) kernel tiles: rows 0..β-1 are the rotated
    digits, row β the rotated P·c0. One rotation at a time: a gather over
    the whole (B, d) batch at once takes tens of times its output in XLA
    temporaries on the chip."""
    H, nbeta, M, N = digits.shape
    Rt, C = core_ntt.tile_shape(N)
    rows = jnp.concatenate([digits, c0e[:, None]], axis=1).reshape(
        H, (nbeta + 1) * M, N)

    def element(slots):
        x = rows[slots[0]]
        return jax.lax.map(
            lambda pm: x[:, pm].reshape(nbeta + 1, M, Rt, C),
            perms[slots[1]])

    return jax.lax.map(element, (ct_slots, diag_slots))


def _fused_kernel(cts_ref, dgs_ref, kid_ref, krow_ref, rot_ref, c0e_ref,
                  c1e_ref, u_ref, *refs, nbeta: int, chunk: int, d: int):
    """The slot and key indirection lives in the BlockSpec index maps (the
    scalar tables are consumed by the DMA engine); dgs_ref also picks the
    is_id entries. ``refs`` holds each rotation's k0 block, then each
    rotation's k1 block, then is_id, q, qneg and the two accumulators."""
    del cts_ref, kid_ref, krow_ref
    rk0_refs, rk1_refs = refs[:chunk], refs[chunk: 2 * chunk]
    id_ref, q_ref, qneg_ref, a0_ref, a1_ref = refs[2 * chunk:]
    b, i, rblk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    q, qn = q_ref[i, 0], qneg_ref[i, 0]

    @pl.when(rblk == 0)
    def _init():
        a0_ref[...] = jnp.zeros(a0_ref.shape, jnp.uint32)
        a1_ref[...] = jnp.zeros(a1_ref.shape, jnp.uint32)

    a0, a1 = a0_ref[...], a1_ref[...]
    c0e, c1e = c0e_ref[...], c1e_ref[...]
    base = dgs_ref[b] * d + rblk * chunk
    for r in range(chunk):                       # rotation-inner loop
        c0r = rot_ref[r, nbeta]
        k0 = k1 = None
        for j in range(nbeta):                   # KeyIP
            dj = rot_ref[r, j]
            t0 = mm.montmul(dj, rk0_refs[r][j], q, qn)
            t1 = mm.montmul(dj, rk1_refs[r][j], q, qn)
            k0 = t0 if k0 is None else mm.montadd(k0, t0, q)
            k1 = t1 if k1 is None else mm.montadd(k1, t1, q)
        is_id = id_ref[base + r] != 0            # z=0: bypass KeyIP
        t0 = jnp.where(is_id, c0e, mm.montadd(k0, c0r, q))
        t1 = jnp.where(is_id, c1e, k1)
        u = u_ref[r]
        a0 = mm.montadd(a0, mm.montmul(u, t0, q, qn), q)   # DiagIP
        a1 = mm.montadd(a1, mm.montmul(u, t1, q, qn), q)
    a0_ref[...] = a0
    a1_ref[...] = a1


def fetch_rows(key_idx, is_id, chunk: int):
    """(S·d,) key-table row each rotation slot's block is fetched from.

    An identity or padding slot (is_id) reads no key, so its slot takes the
    row the same chunk position fetched one rotation chunk earlier: the
    block index does not change between those two grid steps, and the
    pipeline issues no DMA for it. A chunk's first slot at rotation block
    0 follows a step on another limb row, which fetches anyway."""
    S, d = key_idx.shape
    k = key_idx.reshape(S, d // chunk, chunk)
    idle = is_id.reshape(S, d // chunk, chunk) != 0
    cols = [k[:, 0]]
    for r in range(1, d // chunk):
        cols.append(jnp.where(idle[:, r], cols[-1], k[:, r]))
    return jnp.stack(cols, axis=1).reshape(-1)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fused_hlt_indexed(digits, c0e, c1e, u_mont, rk0, rk1, key_idx, key_rows,
                      perms, is_id, ct_slots, diag_slots, q32, qneg, *,
                      chunk: int, interpret: bool):
    """Slot-indexed batched fused HLT over deduped operands and one table
    of rotation keys.

    digits: (H, β, M, N); c0e/c1e: (H, M, N)      — H UNIQUE hoisting products
    u_mont: (S, d, M, N); perms: (S, d, N) i32;
    is_id: (S, d, 1) i32                          — S UNIQUE diagonal sets
    rk0/rk1: (K, β_t, M_t, N)                     — K rotation keys, β_t ≥ β
    key_idx: (S, d) i32                           — slot -> key-table row
    key_rows: (M,) i32                            — extended limb -> table row
    ct_slots / diag_slots: (B,) i32               — batch index -> slot

    u_mont and the key tables may also come as kernel tiles, with the last
    axis split into ``core/ntt.tile_shape(N)``: on the chip the split is a
    layout copy, so operands that stay resident are stored that way.

    Each rotation of a chunk reads its key's first β digits on limb row
    ``key_rows[i]`` straight from the table (one block per rotation, picked
    by a scalar-prefetched row index), so no key is copied per diagonal.
    Returns (acc0, acc1): (B, M, N) accumulated DiagIP in the extended basis.
    """
    H, nbeta, M, N = digits.shape
    B = ct_slots.shape[0]
    d = u_mont.shape[1]
    Rt, C = core_ntt.tile_shape(N)
    u_mont = _as_tiles(u_mont, 4, Rt, C)
    rk0, rk1 = _as_tiles(rk0, 4, Rt, C), _as_tiles(rk1, 4, Rt, C)
    chunk = min(chunk, d)
    assert d % chunk == 0, (d, chunk)
    assert diag_slots.shape == (B,), (diag_slots.shape, B)
    assert rk0.shape[1] >= nbeta and key_rows.shape == (M,), \
        (rk0.shape, nbeta, key_rows.shape, M)
    ct_slots = ct_slots.astype(jnp.int32)
    diag_slots = diag_slots.astype(jnp.int32)
    kid = fetch_rows(key_idx.astype(jnp.int32), is_id, chunk)
    rot = automorph_operands(digits, c0e, perms, ct_slots, diag_slots)
    tl = common.tiles
    rot_s = pl.BlockSpec((None, chunk, nbeta + 1, None, Rt, C),
                         lambda b, i, r, *_: (b, r, 0, i, 0, 0))
    ce_s = pl.BlockSpec((None, None, Rt, C),
                        lambda b, i, _r, cts, *_: (cts[b], i, 0, 0))
    u_s = pl.BlockSpec((None, chunk, None, Rt, C),
                       lambda b, i, r, _c, dgs, *_: (dgs[b], r, i, 0, 0))

    def rk_spec(j):
        return pl.BlockSpec(
            (None, nbeta, None, Rt, C),
            lambda b, i, r, _c, dgs, kid, krow: (
                kid[dgs[b] * d + r * chunk + j], 0, krow[i], 0, 0))

    rk_s = [rk_spec(j) for j in range(chunk)]
    out_s = pl.BlockSpec((None, None, Rt, C),
                         lambda b, i, *_: (b, i, 0, 0))
    smem = common.SMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(B, M, d // chunk),
        in_specs=[rot_s, ce_s, ce_s, u_s, *rk_s, *rk_s, smem, smem, smem],
        out_specs=[out_s, out_s],
    )
    with common.lowering_scope(interpret):
        a0, a1 = pl.pallas_call(
            functools.partial(_fused_kernel, nbeta=nbeta, chunk=chunk, d=d),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, M, Rt, C), jnp.uint32)] * 2,
            interpret=interpret,
        )(ct_slots, diag_slots, kid, key_rows.astype(jnp.int32), rot, tl(c0e),
          tl(c1e), u_mont, *([rk0] * chunk), *([rk1] * chunk),
          is_id.reshape(-1), q32, qneg)
    return common.untiles(a0), common.untiles(a1)


def _as_tiles(x, ndim: int, Rt: int, C: int):
    """``x`` with an (..., N) last axis of ``ndim`` axes as (..., Rt, C)
    tiles; already tiled (``ndim + 1`` axes) it passes as it is."""
    return x if x.ndim == ndim + 1 else x.reshape(x.shape[:-1] + (Rt, C))


def fused_hlt_batched(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id, q32,
                      qneg, *, chunk: int, interpret: bool):
    """Per-element operands along a leading batch axis B (every slot used
    once), with a rotation key per diagonal: rk0/rk1 (B, d, β, M, N) are
    read as a table of B·d keys. Returns (acc0, acc1): (B, M, N)."""
    B, d, nbeta, M, N = rk0.shape
    slots = jnp.arange(B, dtype=jnp.int32)
    return fused_hlt_indexed(
        digits, c0e, c1e, u_mont, rk0.reshape(B * d, nbeta, M, N),
        rk1.reshape(B * d, nbeta, M, N),
        jnp.arange(B * d, dtype=jnp.int32).reshape(B, d),
        jnp.arange(M, dtype=jnp.int32), perms, is_id, slots, slots, q32,
        qneg, chunk=chunk, interpret=interpret)


def fused_hlt(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id, q32, qneg,
              *, chunk: int, interpret: bool):
    """One ciphertext: digits (β, M, N); c0e/c1e (M, N); u_mont (d, M, N);
    rk0/rk1 (d, β, M, N); perms (d, N) i32; is_id (d, 1) i32.
    Returns (acc0, acc1): (M, N)."""
    a0, a1 = fused_hlt_batched(
        digits[None], c0e[None], c1e[None], u_mont[None], rk0[None],
        rk1[None], perms[None], is_id[None], q32, qneg, chunk=chunk,
        interpret=interpret)
    return a0[0], a1[0]
