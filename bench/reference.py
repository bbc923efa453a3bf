"""The plain reference of one encrypted product, its lower-precision
control, and the comparison that decides ``correct``.

The reference is float64 ``A @ B`` in numpy on the host, from the seed's
plaintext inputs; it takes nothing the program made. Each timed product's
decrypted output is compared with it by its root-mean-square error; the
number compared is the worst product's. The control puts the reference,
computed in bfloat16 (inputs and output in bfloat16, as the chip's matrix
unit multiplies), in the program's place: it has to come out not correct.
"""
from __future__ import annotations

import numpy as np


def reference(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    return np.asarray(A, np.float64) @ np.asarray(B, np.float64)


def control(A: np.ndarray, B: np.ndarray) -> np.ndarray:
    """The reference one precision step down, on the default device."""
    import jax.numpy as jnp
    a = jnp.asarray(A, jnp.bfloat16)
    b = jnp.asarray(B, jnp.bfloat16)
    return np.asarray(jnp.dot(a, b, preferred_element_type=jnp.bfloat16),
                      np.float64)


def rms(C: np.ndarray, ref: np.ndarray) -> float:
    return float(np.sqrt(np.mean((np.asarray(C, np.float64) - ref) ** 2)))


def compare(outputs, refs, limits: dict) -> dict:
    """``outputs``: (pair index, decrypted C) of every timed product;
    ``refs``: the reference of each pool pair. Returns the checks, each
    ``{"value", "limit"}``, and the number of products over the limit."""
    errs = [rms(C, refs[i]) for i, C in outputs]
    worst = max(errs) if errs else float("inf")
    limit = float(limits["rms_err"])
    return {"checks": {"rms_err": {"value": worst, "limit": limit}},
            "failed": sum(1 for e in errs if not e <= limit)}
