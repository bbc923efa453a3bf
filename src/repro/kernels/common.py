"""Pallas plumbing shared by every kernel: the coefficient tile layout, the
scalar-memory spec for per-limb constants, and the lowering scope.

Mosaic (the TPU kernel compiler) only accepts blocks whose last two
dimensions are (8, 128)-aligned or whole, and cannot gather along lanes.
So each kernel sees a polynomial as one whole (R, C) tile
(``core/ntt.tile_shape``) under squeezed leading block dimensions, reads its
per-limb scalars (moduli, Montgomery constants, BaseConv weights) from SMEM
by grid index, and the wrappers reshape (..., N) <-> (..., R, C) around the
call.
"""
from __future__ import annotations

import contextlib

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ntt as core_ntt

#: whole-array operand in scalar memory (per-limb constants, read by index)
SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def tiles(x):
    """(..., N) -> (..., R, C) kernel tiles (row-major; on the chip a
    layout copy, so large resident operands are stored tiled)."""
    R, C = core_ntt.tile_shape(x.shape[-1])
    return x.reshape(x.shape[:-1] + (R, C))


def untiles(x):
    """(..., R, C) -> (..., N)."""
    return x.reshape(x.shape[:-2] + (x.shape[-2] * x.shape[-1],))


def lowering_scope(interpret: bool):
    """Trace a Mosaic kernel with 32-bit defaults.

    The package enables x64 globally for its u64 host oracles; under it the
    index maps' integer literals and roll shifts trace as i64, which Mosaic
    rejects. The kernels themselves are u32/i32/f32 on the chip, so the
    compiled path traces them with x64 off; interpret mode keeps x64 so the
    CPU runs can use f64 BaseConv tables.
    """
    return contextlib.nullcontext() if interpret else jax.enable_x64(False)


def i32_to_float(x, dtype):
    """u32 residue (< 2^31) -> float: Mosaic has no unsigned-to-float cast."""
    return x.astype(jax.numpy.int32).astype(dtype)


def float_to_u32(x):
    """Non-negative float count -> u32 via i32 (same reason)."""
    return x.astype(jax.numpy.int32).astype(jax.numpy.uint32)
