"""Measured peak of 32-bit Montgomery modular multiplication on the chip.

v5e publishes no VPU integer peak, so the roofline's compute bound is
measured: a Pallas kernel keeps a block of residues in VMEM and runs an
unrolled chain of Montgomery multiplications on it, in the benchmark's own
copy of the arithmetic (the program's is ``repro.core.modmath``). The rate
is the best of a few timed batches of calls, each of half a second or more
on the host clock. The chain's last value is checked against Python
integers, so the work cannot be optimised away unnoticed.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

Q = 1_073_479_681            # an odd modulus below 2^30, as the program's
QNEG = (-pow(Q, -1, 1 << 32)) % (1 << 32)
ROWS, LANES = 64, 128        # one (64, 128) u32 block: 8 vector registers


def mulhi32(a, b):
    """High word of the 32x32-bit product from 16-bit partial products."""
    mask = jnp.uint32(0xFFFF)
    a0, a1 = a & mask, a >> 16
    b0, b1 = b & mask, b >> 16
    lo = a0 * b0
    m1 = a1 * b0 + (lo >> 16)
    m2 = a0 * b1 + (m1 & mask)
    return a1 * b1 + (m1 >> 16) + (m2 >> 16)


def montmul(a, b, q, qneg):
    """a * b / 2^32 mod q for a, b < q < 2^30, q odd."""
    lo = a * b
    t = mulhi32(a, b) + mulhi32(lo * qneg, q) + (lo != 0).astype(jnp.uint32)
    return jnp.where(t >= q, t - q, t)


def _kernel(x_ref, y_ref, o_ref, *, iters: int, unroll: int):
    q, qneg = jnp.uint32(Q), jnp.uint32(QNEG)
    y = y_ref[...]

    def body(_, x):
        for _ in range(unroll):
            x = montmul(x, y, q, qneg)
        return x
    o_ref[...] = jax.lax.fori_loop(0, iters, body, x_ref[...])


@functools.partial(jax.jit, static_argnames=("iters", "unroll", "interpret"))
def chain(x, y, *, iters: int, unroll: int, interpret: bool):
    """x, y: (blocks * ROWS, LANES) u32. Each element runs iters * unroll
    dependent Montgomery multiplications by its y."""
    spec = pl.BlockSpec((ROWS, LANES), lambda i: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, iters=iters, unroll=unroll),
        grid=(x.shape[0] // ROWS,), in_specs=[spec, spec], out_specs=spec,
        out_shape=jax.ShapeDtypeStruct(x.shape, jnp.uint32),
        interpret=interpret)(x, y)


def expected(x: int, y: int, steps: int) -> int:
    rinv = pow(1 << 32, -1, Q)
    for _ in range(steps):
        x = x * y * rinv % Q
    return x


def measure(blocks: int = 64, iters: int = 512, unroll: int = 8,
            min_batch_s: float = 0.5, batches: int = 3,
            interpret: bool = False) -> dict:
    """Best modular multiplications per second over ``batches`` timed batches
    of calls. Returns ``{"modmul_per_s", "checked"}``."""
    rng = np.random.default_rng(7)
    shape = (blocks * ROWS, LANES)
    xs = rng.integers(1, Q, size=shape, dtype=np.uint64).astype(np.uint32)
    ys = rng.integers(1, Q, size=shape, dtype=np.uint64).astype(np.uint32)
    with jax.enable_x64(False):
        x, y = jnp.asarray(xs), jnp.asarray(ys)
        run = functools.partial(chain, iters=iters, unroll=unroll,
                                interpret=interpret)
        out = np.asarray(jax.block_until_ready(run(x, y)))
        steps = iters * unroll
        if int(out[0, 0]) != expected(int(xs[0, 0]), int(ys[0, 0]), steps):
            raise RuntimeError("modmul peak kernel computed a wrong chain")
        t0 = time.perf_counter()
        jax.block_until_ready(run(x, y))
        one = max(time.perf_counter() - t0, 1e-6)
        calls = max(1, int(np.ceil(min_batch_s / one)))
        best = 0.0
        for _ in range(batches):
            t0 = time.perf_counter()
            for _ in range(calls):
                x = run(x, y)
            jax.block_until_ready(x)
            best = max(best, calls * x.size * steps
                       / (time.perf_counter() - t0))
    return {"modmul_per_s": best, "checked": True}
