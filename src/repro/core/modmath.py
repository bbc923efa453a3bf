"""Modular arithmetic over RNS limbs.

Two backends:

* ``u64`` — reference/CPU path. Coefficients are stored as uint32 (< 2^30
  primes) and upcast to uint64 per-op. Exact, simple, used by the pure-jnp
  oracle implementations (``ref.py`` of every kernel) and by the CPU runtime.

* ``mont`` (u32 Montgomery, R = 2^32) — the TPU-native path. TPU has no
  widening 64-bit integer multiply, so ``mulhi32`` is emulated from 16-bit
  partial products (4 u32 multiplies), and modular multiplication is a
  Montgomery REDC (2 emulated mulhi + 2 mullo). This is the arithmetic the
  Pallas kernels use. Constants (twiddles, evk, plaintext diagonals) are
  pre-converted to the Montgomery domain so that
  ``montmul(x_std, c_mont) == x * c mod q`` with no runtime conversion.

All functions broadcast over leading dims; moduli arrays broadcast against the
trailing coefficient axis (typical shapes: x ``(limbs, N)``, q ``(limbs, 1)``).
"""
from __future__ import annotations

import numpy as np
import jax.numpy as jnp
from jax import lax

U32 = jnp.uint32
U64 = jnp.uint64

# ---------------------------------------------------------------------------
# u64 reference backend
# ---------------------------------------------------------------------------


def mulmod(x, y, q):
    """(x * y) mod q, exact via uint64. x, y uint32; q uint64 (broadcast)."""
    return ((x.astype(U64) * y.astype(U64)) % q).astype(U32)


def addmod(x, y, q):
    s = x.astype(U64) + y.astype(U64)
    s = jnp.where(s >= q, s - q, s)
    return s.astype(U32)


def submod(x, y, q):
    d = x.astype(U64) + q - y.astype(U64)
    d = jnp.where(d >= q, d - q, d)
    return d.astype(U32)


def negmod(x, q):
    return jnp.where(x == 0, x, (q - x.astype(U64)).astype(U32))


# ---------------------------------------------------------------------------
# u32 Montgomery backend (TPU-native; works identically under interpret=True)
# ---------------------------------------------------------------------------


def mulhi32(a, b):
    """High 32 bits of a*b using only u32 ops (16-bit partial products).

    No intermediate overflows:  a1*b0 <= (2^16-1)^2 and the added carry terms
    are < 2^16, so every sum stays below 2^32.
    """
    a = a.astype(U32)
    b = b.astype(U32)
    mask = U32(0xFFFF)
    a0, a1 = a & mask, a >> 16
    b0, b1 = b & mask, b >> 16
    lo = a0 * b0
    m1 = a1 * b0 + (lo >> 16)
    m2 = a0 * b1 + (m1 & mask)
    return a1 * b1 + (m1 >> 16) + (m2 >> 16)


def montmul(a, b, q32, qneg_inv):
    """Montgomery product  a * b * R^{-1} mod q  with R = 2^32.

    a, b in [0, q); q < 2^30 odd; qneg_inv = -q^{-1} mod 2^32 (uint32).
    Output in [0, q). Only u32 multiplies — Pallas/TPU safe.
    """
    a = a.astype(U32)
    b = b.astype(U32)
    lo = a * b                      # x mod R
    hi = mulhi32(a, b)              # x div R
    m = lo * qneg_inv               # mod R
    mq_hi = mulhi32(m, q32)
    # (x + m*q) / R: the low word cancels exactly; carry=1 iff lo != 0.
    carry = (lo != 0).astype(U32)
    t = hi + mq_hi + carry          # < 2q < 2^31, no overflow
    return jnp.where(t >= q32, t - q32, t)


def montadd(a, b, q32):
    s = a + b                       # < 2^31
    return jnp.where(s >= q32, s - q32, s)


def montsub(a, b, q32):
    d = a + q32 - b
    return jnp.where(d >= q32, d - q32, d)


def montsum(x, q32, axis: int = 0):
    """Tree-reduce modular sum along `axis` with montadd (u32-safe).

    log2(n) vectorized halving steps instead of an n-term sequential MAC
    chain — the one reduction shared by the BaseConv kernels and the sharded
    datapath (a 44-limb basis traces as 6 adds, not 44). Returns x with
    `axis` squeezed out.
    """
    n = x.shape[axis]
    while n > 1:
        h = n // 2
        a = lax.slice_in_dim(x, 0, h, axis=axis)
        b = lax.slice_in_dim(x, h, 2 * h, axis=axis)
        s = montadd(a, b, q32)
        if n > 2 * h:           # odd count: carry the last term (Mosaic has
            s = jnp.concatenate(  # no zero-sized slices, so only when present)
                [s, lax.slice_in_dim(x, 2 * h, n, axis=axis)], axis=axis)
        x = s
        n = n - h
    return jnp.squeeze(x, axis=axis)


def to_mont(x, q32, qneg_inv, r2):
    """Standard -> Montgomery domain: x*R mod q (r2 = R^2 mod q)."""
    return montmul(x, r2, q32, qneg_inv)


def from_mont(x, q32, qneg_inv):
    """Montgomery -> standard domain: montmul by 1."""
    return montmul(x, jnp.ones_like(x), q32, qneg_inv)


# ---------------------------------------------------------------------------
# host-side (python int) helpers for table precomputation
# ---------------------------------------------------------------------------


def host_pow(base: int, exp: int, q: int) -> int:
    return pow(base, exp, q)


def host_inv(x: int, q: int) -> int:
    return pow(x, q - 2, q)  # q prime


def mont_constants(q: int) -> tuple[int, int]:
    """Return (qneg_inv, r2) for R=2^32: -q^{-1} mod 2^32 and R^2 mod q."""
    qinv = pow(q, -1, 1 << 32)
    qneg_inv = ((1 << 32) - qinv) & 0xFFFFFFFF
    r2 = (1 << 64) % q
    return qneg_inv, r2


def to_mont_host(x: int, q: int) -> int:
    return (x << 32) % q


def to_mont_host_arr(x: np.ndarray, qs: np.ndarray) -> np.ndarray:
    """Vectorized to_mont_host: (x << 32) % q with broadcasting, as uint32.

    Safe for q < 2^30 residues (x << 32 < 2^62 fits uint64). The one
    Montgomery host encoder shared by every table builder (core/hlt_dist.py,
    precompute paths) — keep byte-identical to the scalar to_mont_host."""
    return ((x.astype(np.uint64) << np.uint64(32)) % qs.astype(np.uint64)
            ).astype(np.uint32)


# ---------------------------------------------------------------------------
# primality / prime search (host)
# ---------------------------------------------------------------------------

_MR_WITNESSES = (2, 3, 5, 7, 11, 13, 17, 19, 23, 29, 31, 37)


def is_prime(n: int) -> bool:
    """Deterministic Miller-Rabin for n < 3.3e24."""
    if n < 2:
        return False
    for p in _MR_WITNESSES:
        if n % p == 0:
            return n == p
    d, r = n - 1, 0
    while d % 2 == 0:
        d //= 2
        r += 1
    for a in _MR_WITNESSES:
        x = pow(a, d, n)
        if x in (1, n - 1):
            continue
        for _ in range(r - 1):
            x = x * x % n
            if x == n - 1:
                break
        else:
            return False
    return True


def gen_ntt_primes(count: int, bits: int, two_n: int, skip: frozenset = frozenset()) -> list[int]:
    """`count` primes q ≡ 1 (mod two_n), q < 2^30, starting just below 2^bits.

    Walks downward so repeated calls with the same args are deterministic.
    """
    assert bits <= 30, "u32 Montgomery path requires q < 2^30"
    out: list[int] = []
    # largest candidate ≡ 1 mod 2N below 2^bits
    q = (1 << bits) - ((1 << bits) - 1) % two_n
    while len(out) < count:
        if q <= two_n:
            raise ValueError(f"ran out of {bits}-bit primes ≡ 1 mod {two_n}")
        if q not in skip and is_prime(q):
            out.append(q)
        q -= two_n
    return out


def find_primitive_root(q: int, two_n: int, rng: np.random.Generator) -> int:
    """ψ of order exactly two_n mod q (requires two_n | q-1)."""
    assert (q - 1) % two_n == 0
    cof = (q - 1) // two_n
    while True:
        x = int(rng.integers(2, q - 1))
        psi = pow(x, cof, q)
        # order divides two_n; exact iff psi^(two_n/2) == -1
        if pow(psi, two_n // 2, q) == q - 1:
            return psi


def bit_reverse_indices(n: int) -> np.ndarray:
    bits = n.bit_length() - 1
    idx = np.arange(n, dtype=np.int64)
    rev = np.zeros_like(idx)
    for b in range(bits):
        rev |= ((idx >> b) & 1) << (bits - 1 - b)
    return rev
