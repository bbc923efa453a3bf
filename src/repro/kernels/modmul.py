"""Element-wise modular multiply/add over RNS limbs — Pallas TPU kernel.

Grid: (N // block,). Per grid step the VMEM working set is one (M, block)
tile of each operand (every limb, block lanes) plus the (M, 1) per-limb
constants — the modular ALU array of the paper's PE, with dp = block lanes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath as mm
from repro.kernels import common

DEFAULT_BLOCK = 1024      # lanes per grid step (multiple of 128)


def _modmul_kernel(x_ref, y_ref, q_ref, qneg_ref, o_ref):
    x = x_ref[...]
    y = y_ref[...]
    q = q_ref[...]
    qneg = qneg_ref[...]
    o_ref[...] = mm.montmul(x, y, q, qneg)


def _modadd_kernel(x_ref, y_ref, q_ref, o_ref):
    o_ref[...] = mm.montadd(x_ref[...], y_ref[...], q_ref[...])


def _call(kernel, data_args, const_args, block, interpret):
    """data_args: (M, N) operands tiled by lanes; const_args: (M, 1)."""
    M, N = data_args[0].shape
    block = min(block, N)
    data = pl.BlockSpec((M, block), lambda j: (0, j))
    const = pl.BlockSpec((M, 1), lambda _j: (0, 0))
    with common.lowering_scope(interpret):
        return pl.pallas_call(
            kernel,
            grid=(N // block,),
            in_specs=[data] * len(data_args) + [const] * len(const_args),
            out_specs=data,
            out_shape=jax.ShapeDtypeStruct((M, N), jnp.uint32),
            interpret=interpret,
        )(*data_args, *const_args)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def modmul(x, y, q32, qneg, *, block: int = DEFAULT_BLOCK,
           interpret: bool):
    """x, y: (M, N) u32; q32/qneg: (M, 1). Montgomery product per limb."""
    return _call(_modmul_kernel, (x, y), (q32, qneg), block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def modadd(x, y, q32, *, block: int = DEFAULT_BLOCK, interpret: bool):
    return _call(_modadd_kernel, (x, y), (q32,), block, interpret)
