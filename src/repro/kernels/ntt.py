"""Per-limb negacyclic NTT — Pallas TPU kernel.

Grid over (batch, limbs): each grid step loads one limb's full polynomial and
its per-stage twiddle tiles into VMEM and runs all log2(N) butterfly stages
in-register/VMEM — the streaming-permutation + ALU pipeline of the paper's PE
collapsed into one resident pass. This is the TPU answer to FPGA fine-grained
reuse: one HBM read + one write per limb per NTT instead of log N round trips.

The butterfly stages live in core/ntt.py (`ntt_tile` / `intt_tile`, the
tiled form of `ntt_mont_raw` / `intt_mont_raw`); the kernels only contribute
the VMEM residency/grid structure (kernels/common.py has the tile layout).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import ntt as core_ntt
from repro.kernels import common


def _ntt_kernel(x_ref, tw_ref, q_ref, qneg_ref, o_ref):
    i = pl.program_id(1)
    o_ref[...] = core_ntt.ntt_tile(x_ref[...], tw_ref[...], q_ref[i, 0],
                                   qneg_ref[i, 0])


def _intt_kernel(x_ref, tw_ref, ninv_ref, q_ref, qneg_ref, o_ref):
    i = pl.program_id(1)
    o_ref[...] = core_ntt.intt_tile(x_ref[...], tw_ref[...], ninv_ref[i, 0],
                                    q_ref[i, 0], qneg_ref[i, 0])


def _call(kernel, x, tw, consts, interpret):
    B, M, N = x.shape
    R, C = core_ntt.tile_shape(N)
    S = tw.shape[1]
    poly = pl.BlockSpec((None, None, R, C), lambda b, i: (b, i, 0, 0))
    tws = pl.BlockSpec((None, S, R, C), lambda _b, i: (i, 0, 0, 0))
    with common.lowering_scope(interpret):
        out = pl.pallas_call(
            kernel,
            grid=(B, M),
            in_specs=[poly, tws] + [common.SMEM] * len(consts),
            out_specs=poly,
            out_shape=jax.ShapeDtypeStruct((B, M, R, C), jnp.uint32),
            interpret=interpret,
        )(common.tiles(x), tw, *consts)
    return common.untiles(out)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ntt(x, psi_m, q32, qneg, *, interpret: bool):
    """x: (B, M, N) u32 std-domain coeffs; psi_m: (M, N) Montgomery twiddles;
    q32/qneg: (M, 1). Returns bit-reversed eval order, std domain."""
    return _call(_ntt_kernel, x, core_ntt.expand_twiddles(psi_m),
                 (q32, qneg), interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def intt(x, psii_m, ninv_m, q32, qneg, *, interpret: bool):
    return _call(_intt_kernel, x,
                 core_ntt.expand_twiddles(psii_m, inverse=True),
                 (ninv_m, q32, qneg), interpret)
