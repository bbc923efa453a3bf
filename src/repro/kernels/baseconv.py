"""RNS BaseConv — Pallas TPU kernel.

The one limb-coupling sub-operation (ModUp/ModDown). Grid: (⌈N/block⌉,)
— non-block-multiple N is handled by the clamped last tile (columnwise-pure
kernel, so recomputed overlap columns are bit-identical).
Each step loads ALL source limbs for one coefficient tile (|S| ≤ ~44 rows —
a (|S|, block) VMEM tile) and the whole (|T|, |S|) W table, and emits every
target limb's tile. The f32 overflow-correction term v is computed in-tile.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import modmath as mm
from repro.kernels import common

DEFAULT_BLOCK = 2048


def _baseconv_kernel(x_ref, hatinv_ref, qown_ref, qnegown_ref, w_ref,
                     dmod_ref, invd_ref, qgen_ref, qneggen_ref, o_ref):
    x = x_ref[...]                                # (|S|, blk)
    y = mm.montmul(x, hatinv_ref[...], qown_ref[...], qnegown_ref[...])
    f = common.i32_to_float(y, jnp.float32) * invd_ref[...].astype(
        jnp.float32)
    v = common.float_to_u32(jnp.floor(
        jnp.sum(f, axis=0, keepdims=True) + 0.5e-6))           # (1, blk)
    qg = qgen_ref[...]                            # (|T|, 1)
    qneg = qneggen_ref[...]
    w = w_ref[...]                                # (|T|, |S|)
    acc = None
    for s in range(y.shape[0]):                   # MAC over source limbs
        term = mm.montmul(y[s:s + 1], w[:, s:s + 1], qg, qneg)  # (|T|, blk)
        acc = term if acc is None else mm.montadd(acc, term, qg)
    corr = mm.montmul(v, dmod_ref[...], qg, qneg)
    o_ref[...] = mm.montsub(acc, corr, qg)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def baseconv(x, hat_inv_m, q_own, qneg_own, W_m, D_mod_m, inv_d, q_gen,
             qneg_gen, *, block: int = DEFAULT_BLOCK, interpret: bool):
    """x: (|S|, N); hat_inv_m/q_own/qneg_own: (|S|, 1);
    W_m: (|T|, |S|) mont; D_mod_m/q_gen/qneg_gen: (|T|, 1); inv_d: (|S|, 1)
    float. Returns (|T|, N) u32 residues over the target basis."""
    ns, N = x.shape
    nt = W_m.shape[0]
    block = min(block, N)
    src = pl.BlockSpec((ns, block), lambda j: (0, j))
    whole = lambda a: pl.BlockSpec(a.shape, lambda _j: (0, 0))
    out = pl.BlockSpec((nt, block), lambda j: (0, j))
    with common.lowering_scope(interpret):
        return pl.pallas_call(
            _baseconv_kernel,
            grid=(pl.cdiv(N, block),),
            in_specs=[src] + [whole(a) for a in (hat_inv_m, q_own, qneg_own,
                                                 W_m, D_mod_m, inv_d, q_gen,
                                                 qneg_gen)],
            out_specs=out,
            out_shape=jax.ShapeDtypeStruct((nt, N), jnp.uint32),
            interpret=interpret,
        )(x, hat_inv_m, q_own, qneg_own, W_m, D_mod_m, inv_d, q_gen, qneg_gen)
