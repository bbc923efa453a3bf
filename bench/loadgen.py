"""Inputs of a cell, drawn from ``--seed`` as its traffic mix says.

One generator for every mix: a mix file gives the loop, the products in
flight, the size of the input pool and the warm-up products. Each seed
draws its own keys, inputs and encryption noise from separate streams, so
two runs of one seed see the same keys, the same matrices and the same
ciphertexts.
"""
from __future__ import annotations

import numpy as np

KEYS, DATA, NOISE = 0, 1, 2     # stream ids under one seed


def rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def check_mix(traffic: dict) -> None:
    if traffic.get("loop") != "closed" or traffic.get("in_flight") != 1:
        raise ValueError(f"this generator drives a closed loop with one "
                         f"product in flight, not {traffic}")
    if int(traffic["pool_pairs"]) < 1 or int(traffic["warmup_products"]) < 1:
        raise ValueError(f"pool_pairs and warmup_products must be >= 1: "
                         f"{traffic}")


def pool(seed: int, shape, traffic: dict) -> list:
    """``pool_pairs`` distinct (A, B) float64 pairs, uniform in [-1, 1]:
    A is m x l and B is l x n for ``shape`` = (m, l, n)."""
    m, l, n = shape
    r = rng(seed, DATA)
    return [(r.uniform(-1, 1, (m, l)), r.uniform(-1, 1, (l, n)))
            for _ in range(int(traffic["pool_pairs"]))]


def pair_of(product: int, traffic: dict) -> int:
    """Pool index of the ``product``-th product of a run (warm-up first)."""
    return product % int(traffic["pool_pairs"])
