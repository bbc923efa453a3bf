"""The work one HE matrix product's HLT steps must do: modular
multiplications and HBM bytes, counted from the plan's real diagonals.

This is the benchmark's own count, kept apart from the program's cost model
so that no change to the program moves it. It counts what the algorithm
needs and nothing an implementation adds: unpadded diagonal counts, each
distinct rotation key read once per launch, the diagonals, the input and
output ciphertexts once. Padding rotations, per-set key copies and the
Automorph staging are never counted, so removing them raises the roofline
share instead of moving the yardstick.

One HLT launch at level l over a batch of diagonal sets, with H distinct
input ciphertexts, in a CKKS set (N, L, k, beta):

- alpha = ceil((L + 1) / beta) limbs per digit, nb = ceil((l + 1) / alpha)
  digits, E = l + 1 + k limbs in the extended basis, and an NTT over r limbs
  costs r * N/2 * log2(N) modular multiplications;
- hoist, per input: the inverse NTT of c1 over l + 1 limbs; per digit of
  a limbs, BaseConv to the E - a other limbs (a*N + a*(E - a)*N) and their
  NTT; P*c0 and P*c1 (2*(l + 1)*N);
- rotation loop, per batch element: KeyIP 2*nb*E*N for each diagonal whose
  rotation is not the identity, DiagIP 2*E*N for every diagonal;
- merged ModDown and Rescale, per batch element and each of its 2 polys:
  the inverse NTT of the k + 1 dropped limbs, BaseConv to the l kept limbs
  ((k + 1)*N + l*(k + 1)*N), their NTT and the final multiply by 1/P (l*N).

Bytes: the H inputs (2*(l + 1)*N words each), each distinct rotation key
(2*nb*E*N words), every diagonal (E*N words) and the outputs (2*l*N words
each), in 4-byte words.
"""
from __future__ import annotations

import math

WORD = 4    # bytes of one u32 residue


def _ntt(limbs: int, N: int) -> int:
    return limbs * (N // 2) * int(math.log2(N))


def hlt_launch(N: int, L: int, k: int, beta: int, level: int,
               diag_sets, n_inputs: int) -> dict:
    """Work of one batched HLT launch.

    ``diag_sets``: the rotation offsets ``zs`` of each batch element's
    diagonal set (real diagonals only). Returns ``{"modmults", "bytes"}``.
    """
    alpha = math.ceil((L + 1) / beta)
    digits = [min(alpha, level + 1 - s) for s in range(0, level + 1, alpha)]
    nb, E = len(digits), level + 1 + k
    hoist = (_ntt(level + 1, N)
             + sum(a * N + a * (E - a) * N + _ntt(E - a, N) for a in digits)
             + 2 * (level + 1) * N)
    moddown = 2 * (_ntt(k + 1, N) + (k + 1) * N + level * (k + 1) * N
                   + _ntt(level, N) + level * N)
    rotations = sum(sum(1 for z in zs if z != 0) for zs in diag_sets)
    diagonals = sum(len(zs) for zs in diag_sets)
    modmults = (n_inputs * hoist + rotations * 2 * nb * E * N
                + diagonals * 2 * E * N + len(diag_sets) * moddown)
    slots = N // 2
    keys = {z % slots for zs in diag_sets for z in zs if z % slots != 0}
    words = (n_inputs * 2 * (level + 1) * N + len(keys) * 2 * nb * E * N
             + diagonals * E * N + len(diag_sets) * 2 * level * N)
    return {"modmults": modmults, "bytes": words * WORD}


def hemm(params: dict, step1_sets, step2_sets) -> dict:
    """Work of both HLT steps of Algorithm 2 at the top level L: Step 1
    (sigma(A), tau(B)) at level L and Step 2 (the 2*l eps/omega sets) at
    level L - 1, each with two distinct inputs."""
    N, L, k, beta = (1 << params["logN"], params["L"], params["k"],
                     params["beta"])
    one = hlt_launch(N, L, k, beta, L, step1_sets, 2)
    two = hlt_launch(N, L, k, beta, L - 1, step2_sets, 2)
    return {key: one[key] + two[key] for key in one}


def least_time_s(work: dict, modmul_per_s: float, bytes_per_s: float):
    """(seconds, bound): the larger of the compute and the memory time, and
    which of the two ("modmul" | "hbm") it is."""
    t_mm = work["modmults"] / modmul_per_s
    t_mem = work["bytes"] / bytes_per_s
    return (t_mm, "modmul") if t_mm >= t_mem else (t_mem, "hbm")
