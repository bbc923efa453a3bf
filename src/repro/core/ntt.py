"""Negacyclic NTT / iNTT over RNS limbs (vectorized, limb-batched).

Longa–Naehrig iterative formulation: forward NTT is Cooley–Tukey
decimation-in-time taking natural-order input to *bit-reversed* evaluation
order; inverse is Gentleman–Sande taking bit-reversed back to natural. All
evaluation-domain data in this codebase lives in bit-reversed order; pointwise
products and automorphism tables are consistent with that convention
(verified numerically in tests/test_ntt.py).

The stage loop is a Python loop over log2(N) reshape/butterfly steps — under
jit this unrolls into a fixed dataflow graph. The `*_raw` impls below are
shape-polymorphic (any leading dims, (M, 1) moduli); XLA call sites go
through the public `jax.jit` wrappers, and the Pallas kernels run the same
stages in tiled form (`ntt_tile` / `intt_tile`, bit-identical, tested
against these). The wrappers are deliberately *named* jits: every XLA lowering of
an NTT shows up in a traced jaxpr as a `jit` eqn whose name is one of
`NTT_EQN_NAMES`, which is how the JX004 linter rule (analysis/jaxpr_lint.py)
proves a fused datapath contains no XLA-lowered NTT.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from repro.core import modmath as mm

#: `jit` eqn names produced by the public wrappers — the JX004 census keys.
NTT_EQN_NAMES = frozenset({"ntt", "intt", "ntt_mont", "intt_mont"})


def _as3(q):
    """(M,1) modulus column -> (M,1,1) for (…,M,m,t)-shaped butterfly views."""
    return q[..., None]


def ntt_raw(x, psi_brv, q):
    """Forward negacyclic NTT (unjitted stage recursion).

    x: (..., M, N) uint32, natural order coefficients.
    psi_brv: (M, N) uint32 table ψ^br(i).
    q: (M, 1) uint64 moduli.
    Returns (..., M, N) in bit-reversed evaluation order.
    """
    N = x.shape[-1]
    m, t = 1, N
    q3 = _as3(q)
    while m < N:
        t //= 2
        xv = x.reshape(x.shape[:-1] + (m, 2, t))
        s = psi_brv[..., m:2 * m][..., None]          # (M, m, 1)
        u = xv[..., 0, :]
        v = mm.mulmod(xv[..., 1, :], s, q3)
        x = jnp.stack([mm.addmod(u, v, q3), mm.submod(u, v, q3)], axis=-2)
        x = x.reshape(x.shape[:-3] + (N,))
        m *= 2
    return x


def intt_raw(x, psi_inv_brv, n_inv, q):
    """Inverse negacyclic NTT: bit-reversed eval order -> natural coeffs."""
    N = x.shape[-1]
    q3 = _as3(q)
    h, t = N // 2, 1
    while h >= 1:
        xv = x.reshape(x.shape[:-1] + (h, 2, t))
        s = psi_inv_brv[..., h:2 * h][..., None]
        u = xv[..., 0, :]
        v = xv[..., 1, :]
        x = jnp.stack(
            [mm.addmod(u, v, q3), mm.mulmod(mm.submod(u, v, q3), s, q3)],
            axis=-2,
        )
        x = x.reshape(x.shape[:-3] + (N,))
        t *= 2
        h //= 2
    return mm.mulmod(x, n_inv, q)


def ntt_mont_raw(x, psi_brv_mont, q32, qneg_inv):
    """Forward NTT on the u32 Montgomery datapath (twiddles pre-Montgomeryized,
    data stays in the standard domain throughout)."""
    N = x.shape[-1]
    m, t = 1, N
    q3, qi3 = _as3(q32), _as3(qneg_inv)
    while m < N:
        t //= 2
        xv = x.reshape(x.shape[:-1] + (m, 2, t))
        s = psi_brv_mont[..., m:2 * m][..., None]
        u = xv[..., 0, :]
        v = mm.montmul(xv[..., 1, :], s, q3, qi3)
        x = jnp.stack([mm.montadd(u, v, q3), mm.montsub(u, v, q3)], axis=-2)
        x = x.reshape(x.shape[:-3] + (N,))
        m *= 2
    return x


def intt_mont_raw(x, psi_inv_brv_mont, n_inv_mont, q32, qneg_inv):
    """Inverse NTT on the u32 Montgomery datapath."""
    N = x.shape[-1]
    q3, qi3 = _as3(q32), _as3(qneg_inv)
    h, t = N // 2, 1
    while h >= 1:
        xv = x.reshape(x.shape[:-1] + (h, 2, t))
        s = psi_inv_brv_mont[..., h:2 * h][..., None]
        u = xv[..., 0, :]
        v = xv[..., 1, :]
        x = jnp.stack(
            [mm.montadd(u, v, q3),
             mm.montmul(mm.montsub(u, v, q3), s, q3, qi3)],
            axis=-2,
        )
        x = x.reshape(x.shape[:-3] + (N,))
        t *= 2
        h //= 2
    return mm.montmul(x, n_inv_mont, q32, qneg_inv)


# ---------------------------------------------------------------------------
# tiled in-kernel NTT (the Pallas kernels' form of the recursions above)
# ---------------------------------------------------------------------------
#
# Mosaic cannot reshape a flat (N,) row into the (m, 2, t) butterfly views
# above, so the kernels hold a polynomial as an (R, C) tile (C = min(N, 128)
# lanes, row-major: coefficient j sits at (j // C, j % C)) and run each stage
# on the whole tile: the butterfly partner j ^ t is a lane roll when t < C and
# a sublane roll otherwise, and every position reads its stage twiddle from a
# pre-expanded (log2 N, R, C) table. Bit-identical to ntt_mont_raw /
# intt_mont_raw (tests/test_kernels.py, tests/test_fused_datapath.py).


def tile_shape(N: int) -> tuple[int, int]:
    """(rows, lanes) of one polynomial as a kernel tile."""
    C = min(N, 128)
    return N // C, C


def _stage_index(N: int, inverse: bool) -> np.ndarray:
    """(log2 N, N) twiddle-table index of every coefficient at every stage."""
    logn = N.bit_length() - 1
    j = np.arange(N)
    rows = []
    for s in range(logn):
        if inverse:              # Gentleman–Sande: h = N/2^(s+1), t = 2^s
            rows.append((N >> (s + 1)) + (j >> (s + 1)))
        else:                    # Cooley–Tukey: m = 2^s, t = N/2^(s+1)
            rows.append((1 << s) + (j >> (logn - s)))
    return np.stack(rows)


def expand_twiddles(tw, inverse: bool = False):
    """(..., N) Longa–Naehrig twiddle rows -> (..., log2 N, R, C) per-stage
    tiles for ``ntt_tile`` / ``intt_tile`` (numpy in, numpy out; jnp in,
    jnp out)."""
    N = tw.shape[-1]
    R, C = tile_shape(N)
    out = tw[..., _stage_index(N, inverse)]
    return out.reshape(tw.shape[:-1] + (N.bit_length() - 1, R, C))


def _partner(x, t: int):
    """(x[j ^ t], is_lower) over an (R, C) tile: lower butterfly halves read
    j + t, upper halves j - t (pltpu.roll rotates like jnp.roll)."""
    R, C = x.shape
    if t < C:
        axis, k, size = 1, t, C
    else:
        axis, k, size = 0, t // C, R
    idx = jax.lax.broadcasted_iota(jnp.int32, (R, C), axis)
    lower = (idx & k) == 0
    return (jnp.where(lower, pltpu.roll(x, size - k, axis),
                      pltpu.roll(x, k, axis)), lower)


def ntt_tile(x, tw, q, qneg):
    """Forward NTT of one (R, C) tile; tw is ``expand_twiddles(psi_mont)``."""
    R, C = x.shape
    N = R * C
    t, s = N, 0
    while t > 1:
        t //= 2
        xp, lower = _partner(x, t)
        u = jnp.where(lower, x, xp)
        wv = mm.montmul(jnp.where(lower, xp, x), tw[s], q, qneg)
        x = jnp.where(lower, mm.montadd(u, wv, q), mm.montsub(u, wv, q))
        s += 1
    return x


def intt_tile(x, tw, n_inv, q, qneg):
    """Inverse NTT of one (R, C) tile; tw is
    ``expand_twiddles(psi_inv_mont, inverse=True)``."""
    R, C = x.shape
    N = R * C
    t, s = 1, 0
    while t < N:
        xp, lower = _partner(x, t)
        u = jnp.where(lower, x, xp)
        v = jnp.where(lower, xp, x)
        diff = mm.montmul(mm.montsub(u, v, q), tw[s], q, qneg)
        x = jnp.where(lower, mm.montadd(u, v, q), diff)
        t *= 2
        s += 1
    return mm.montmul(x, n_inv, q, qneg)


def _named_jit(fn, name):
    """jit `fn` so its call sites trace as a `jit` eqn named `name`."""
    fn.__name__ = name
    fn.__qualname__ = name
    return jax.jit(fn)


ntt = _named_jit(lambda x, psi_brv, q: ntt_raw(x, psi_brv, q), "ntt")
intt = _named_jit(
    lambda x, psi_inv_brv, n_inv, q: intt_raw(x, psi_inv_brv, n_inv, q),
    "intt")
ntt_mont = _named_jit(
    lambda x, psi_m, q32, qneg: ntt_mont_raw(x, psi_m, q32, qneg),
    "ntt_mont")
intt_mont = _named_jit(
    lambda x, psii_m, ninv_m, q32, qneg:
        intt_mont_raw(x, psii_m, ninv_m, q32, qneg),
    "intt_mont")
