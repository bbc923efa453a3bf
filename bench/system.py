"""The system under test, driven the way a user drives it.

A copy of ``chip_smoke.py``'s flow (it is not imported, so it can change):
``HEContext(verify="error")`` -> ``plan_hemm`` (through the plan cache) ->
``keygen`` -> ``encrypt_matrix`` on the client -> ``compile_hemm`` with the
cost model's schedule -> ``HEMMProgram.__call__`` -> ``decrypt_matrix`` on
the client. Only ``product`` is timed.
"""
from __future__ import annotations

import time

import numpy as np

from bench import loadgen, plancache


class SetupError(RuntimeError):
    """The program did not take the path the cell measures."""


def peak_bytes() -> int:
    """Peak bytes in use so far on the fullest chip (0 where the backend
    keeps no count)."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class HemmCell:
    """Set-up of one cell for one seed: keys, the encrypted input pool and
    the compiled program. ``phases`` holds each set-up phase's host seconds
    and ``peaks`` the chip's peak bytes at the end of each phase."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro.core.ckks import CkksEngine
        from repro.core.compile import HEContext, compile_hemm
        from repro.core.hemm import encrypt_matrix
        from repro.core.params import HEParams

        loadgen.check_mix(traffic)
        self.traffic = traffic
        self.shape = tuple(config["shape"])
        self.phases, self.peaks = {}, {}
        clock = time.perf_counter

        t = clock()
        eng = CkksEngine(HEParams(**config["params"]))
        self.ctx = HEContext(eng, verify="error")
        self.plan, self.plan_cached = plancache.load_or_build(
            eng, config["params"], self.shape)
        self.phases["plan_s"] = clock() - t
        self.peaks["plan"] = peak_bytes()

        t = clock()
        self.ctx.keygen(loadgen.rng(seed, loadgen.KEYS),
                        rot_steps=self.plan.rot_steps)
        jax.block_until_ready(self.ctx.keys.s_eval)
        self.phases["keygen_s"] = clock() - t
        self.peaks["keygen"] = peak_bytes()

        t = clock()
        self.pairs = loadgen.pool(seed, self.shape, traffic)
        noise = loadgen.rng(seed, loadgen.NOISE)
        keys = self.ctx.keys
        self.cts = [(encrypt_matrix(eng, keys, A, noise),
                     encrypt_matrix(eng, keys, B, noise))
                    for A, B in self.pairs]
        jax.block_until_ready([(a.c0, a.c1, b.c0, b.c1) for a, b in self.cts])
        self.phases["encrypt_s"] = clock() - t
        self.peaks["encrypt"] = peak_bytes()

        t = clock()
        self.prog = compile_hemm(self.ctx, self.plan)
        self.phases["compile_s"] = clock() - t
        self.peaks["compile"] = peak_bytes()
        for name, st in (("step1", self.prog.plan.step1),
                         ("step2", self.prog.plan.step2)):
            if (st.schedule, st.datapath) != ("pallas", "pallas"):
                raise SetupError(f"{name} chose {st.schedule}/{st.datapath}, "
                                 "not the fused pallas/pallas path")

        t = clock()
        for i in range(int(traffic["warmup_products"])):
            self.product(i)
        self.phases["warmup_s"] = clock() - t
        self.peaks["warmup"] = peak_bytes()
        self.next = int(traffic["warmup_products"])

    def product(self, i: int):
        """The timed path: one encrypted product of pool pair ``i``, waited
        for on the device."""
        import jax
        ctA, ctB = self.cts[loadgen.pair_of(i, self.traffic)]
        out = self.prog(ctA, ctB)
        jax.block_until_ready((out.c0, out.c1))
        return out

    def decrypt(self, out) -> np.ndarray:
        from repro.core.hemm import decrypt_matrix
        m, _, n = self.shape
        return decrypt_matrix(self.ctx.eng, self.ctx.keys, out, m, n)

    def step_sets(self):
        """Real diagonal offsets of each batch element of Step 1 and Step 2,
        as the plan holds them (for the work count)."""
        p = self.plan
        return ([p.ds_sigma.zs, p.ds_tau.zs],
                [ds.zs for ds in list(p.ds_eps) + list(p.ds_omega)])
