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
are stored once per UNIQUE hoisting product and the rotation keys / diagonals
once per UNIQUE diagonal set; two scalar-prefetch index vectors (ct_slots,
diag_slots) map batch index -> slot, and the BlockSpec index maps read them
(pltpu.PrefetchScalarGridSpec), so batch element b DMAs the key/diagonal
tiles of slot diag_slots[b] straight from the unique-operand arrays — hemm
Step-2 runs 2·l HLTs off 2·l stored diagonal sets and block MM
σ/τ-transforms every tile off ONE stored key/diagonal set per transform.
``fused_hlt`` (one ciphertext) and ``fused_hlt_batched`` (per-element
operands) are the same kernel with trivial slot maps.
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


def _fused_kernel(cts_ref, dgs_ref, rot_ref, c0e_ref, c1e_ref, u_ref,
                  rk0_ref, rk1_ref, id_ref, q_ref, qneg_ref, a0_ref, a1_ref,
                  *, nbeta: int, chunk: int, d: int):
    """The slot indirection lives in the BlockSpec index maps (cts_ref is
    consumed by the DMA engine); dgs_ref also picks the is_id entries."""
    del cts_ref
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
            t0 = mm.montmul(dj, rk0_ref[r, j], q, qn)
            t1 = mm.montmul(dj, rk1_ref[r, j], q, qn)
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


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def fused_hlt_indexed(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id,
                      ct_slots, diag_slots, q32, qneg, *, chunk: int,
                      interpret: bool):
    """Slot-indexed batched fused HLT over deduped operands.

    digits: (H, β, M, N); c0e/c1e: (H, M, N)      — H UNIQUE hoisting products
    u_mont: (S, d, M, N); rk0/rk1: (S, d, β, M, N);
    perms: (S, d, N) i32; is_id: (S, d, 1) i32    — S UNIQUE diagonal sets
    ct_slots / diag_slots: (B,) i32               — batch index -> slot

    Returns (acc0, acc1): (B, M, N) accumulated DiagIP in the extended basis.
    """
    H, nbeta, M, N = digits.shape
    B = ct_slots.shape[0]
    d = u_mont.shape[1]
    chunk = min(chunk, d)
    assert d % chunk == 0, (d, chunk)
    assert diag_slots.shape == (B,), (diag_slots.shape, B)
    ct_slots = ct_slots.astype(jnp.int32)
    diag_slots = diag_slots.astype(jnp.int32)
    rot = automorph_operands(digits, c0e, perms, ct_slots, diag_slots)
    tl = common.tiles
    Rt, C = core_ntt.tile_shape(N)
    rot_s = pl.BlockSpec((None, chunk, nbeta + 1, None, Rt, C),
                         lambda b, i, r, _c, _g: (b, r, 0, i, 0, 0))
    ce_s = pl.BlockSpec((None, None, Rt, C),
                        lambda b, i, _r, cts, _g: (cts[b], i, 0, 0))
    u_s = pl.BlockSpec((None, chunk, None, Rt, C),
                       lambda b, i, r, _c, dgs: (dgs[b], r, i, 0, 0))
    rk_s = pl.BlockSpec((None, chunk, nbeta, None, Rt, C),
                        lambda b, i, r, _c, dgs: (dgs[b], r, 0, i, 0, 0))
    out_s = pl.BlockSpec((None, None, Rt, C),
                         lambda b, i, _r, _c, _g: (b, i, 0, 0))
    smem = common.SMEM
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, M, d // chunk),
        in_specs=[rot_s, ce_s, ce_s, u_s, rk_s, rk_s, smem, smem, smem],
        out_specs=[out_s, out_s],
    )
    with common.lowering_scope(interpret):
        a0, a1 = pl.pallas_call(
            functools.partial(_fused_kernel, nbeta=nbeta, chunk=chunk, d=d),
            grid_spec=grid_spec,
            out_shape=[jax.ShapeDtypeStruct((B, M, Rt, C), jnp.uint32)] * 2,
            interpret=interpret,
        )(ct_slots, diag_slots, rot, tl(c0e), tl(c1e), tl(u_mont), tl(rk0),
          tl(rk1), is_id.reshape(-1), q32, qneg)
    return common.untiles(a0), common.untiles(a1)


def fused_hlt(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id, q32, qneg,
              *, chunk: int, interpret: bool):
    """One ciphertext: digits (β, M, N); c0e/c1e (M, N); u_mont (d, M, N);
    rk0/rk1 (d, β, M, N); perms (d, N) i32; is_id (d, 1) i32.
    Returns (acc0, acc1): (M, N)."""
    zero = jnp.zeros((1,), jnp.int32)
    a0, a1 = fused_hlt_indexed(
        digits[None], c0e[None], c1e[None], u_mont[None], rk0[None],
        rk1[None], perms[None], is_id[None], zero, zero, q32, qneg,
        chunk=chunk, interpret=interpret)
    return a0[0], a1[0]


def fused_hlt_batched(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id, q32,
                      qneg, *, chunk: int, interpret: bool):
    """Per-element operands along a leading batch axis B (every slot used
    once). Returns (acc0, acc1): (B, M, N)."""
    slots = jnp.arange(digits.shape[0], dtype=jnp.int32)
    return fused_hlt_indexed(digits, c0e, c1e, u_mont, rk0, rk1, perms,
                             is_id, slots, slots, q32, qneg, chunk=chunk,
                             interpret=interpret)
