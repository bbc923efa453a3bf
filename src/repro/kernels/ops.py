"""Public wrappers for the Pallas kernels.

Kernels run compiled (``interpret=False``) whenever the default backend is a
TPU, and in Pallas interpret mode everywhere else (the CPU test runs).
ref.py holds the pure-jnp oracles used by the allclose tests.
"""
from __future__ import annotations

import jax

from repro.kernels import baseconv as _baseconv
from repro.kernels import fused_hlt as _fused
from repro.kernels import modmul as _modmul
from repro.kernels import ntt as _ntt


def _interp() -> bool:
    return jax.default_backend() != "tpu"


def modmul(x, y, q32, qneg, block: int = _modmul.DEFAULT_BLOCK):
    return _modmul.modmul(x, y, q32, qneg, block=block, interpret=_interp())


def modadd(x, y, q32, block: int = _modmul.DEFAULT_BLOCK):
    return _modmul.modadd(x, y, q32, block=block, interpret=_interp())


def ntt(x, psi_m, q32, qneg):
    return _ntt.ntt(x, psi_m, q32, qneg, interpret=_interp())


def intt(x, psii_m, ninv_m, q32, qneg):
    return _ntt.intt(x, psii_m, ninv_m, q32, qneg, interpret=_interp())


def fused_hlt(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id, q32, qneg,
              chunk: int = 8):
    return _fused.fused_hlt(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id,
                            q32, qneg, chunk=chunk, interpret=_interp())


def fused_hlt_batched(digits, c0e, c1e, u_mont, rk0, rk1, perms, is_id, q32,
                      qneg, chunk: int = 8):
    return _fused.fused_hlt_batched(digits, c0e, c1e, u_mont, rk0, rk1, perms,
                                    is_id, q32, qneg, chunk=chunk,
                                    interpret=_interp())


def fused_hlt_indexed(digits, c0e, c1e, u_mont, rk0, rk1, key_idx, key_rows,
                      perms, is_id, ct_slots, diag_slots, q32, qneg,
                      chunk: int = 8):
    return _fused.fused_hlt_indexed(digits, c0e, c1e, u_mont, rk0, rk1,
                                    key_idx, key_rows, perms, is_id, ct_slots,
                                    diag_slots, q32, qneg, chunk=chunk,
                                    interpret=_interp())


def baseconv(x, hat_inv_m, q_own, qneg_own, W_m, D_mod_m, inv_d, q_gen,
             qneg_gen, block: int = _baseconv.DEFAULT_BLOCK):
    return _baseconv.baseconv(x, hat_inv_m, q_own, qneg_own, W_m, D_mod_m,
                              inv_d, q_gen, qneg_gen, block=block,
                              interpret=_interp())
