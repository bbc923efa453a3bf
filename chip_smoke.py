#!/usr/bin/env python3
"""Chip smoke test: one encrypted matrix multiplication at full Set-A width.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --four-chip  # one four-chip host (data 2 × model 2)

Drives the user's path — ``HEContext`` → ``plan_hemm`` → ``keygen`` →
``encrypt_matrix`` → ``compile_hemm`` → one call → ``decrypt_matrix`` — on
the paper's Set-A (N = 2^13, L = 4, k = 1) through its noise-sane runtime
twin (β = 5), at Table III type IV (64×64×64), with random inputs drawn from
a fixed seed.

One chip: the cost model must pick the fused Pallas schedule and datapath
for both HLT steps, the compiled Step-2 program must contain the Pallas
kernel (``tpu_custom_call``), and the decrypted product must match float64
``A @ B`` within ``TOLERANCE``. The same ciphertexts also run through the
u64 XLA reference schedule (``mo``) and the number of output coefficients
that differ is printed.

``--four-chip``: the same product with ``schedule="sharded"`` on a
data 2 × model 2 mesh, compared with the one-chip ``pallas`` result and with
float64; every device must hold its shard of the program's operands.

Exits non-zero and prints no ``ok`` line when JAX finds no TPU, when any
check fails or when any phase raises. On success the last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

SEED = 0
SHAPE = (64, 64, 64)          # configs/fame_sets.py MM_BENCHMARKS set-a type-iv
#: Max |C - A·B| over the 64×64 output. The fused path must be as precise
#: as the u64 reference schedule (``mo``), whose own error is CKKS noise:
#: it grows about 9× for every 4× in N and 2× in l (CPU runs of ``mo`` give
#: 2.0e-4 at N = 2^9 with 16³ and 1.8e-3 at N = 2^11 with 32³), and on this
#: configuration and seed it is 0.0315 on a TPU v5e. 2^-4 leaves the fused
#: path twice the reference's error.
TOLERANCE = 2.0 ** -4


def log(msg: str) -> None:
    print(msg, flush=True)


def _device():
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def build(params):
    """Context + plan + keys + the seeded input matrices and ciphertexts."""
    from repro.core.ckks import CkksEngine
    from repro.core.compile import HEContext
    from repro.core.hemm import encrypt_matrix, plan_hemm

    rng = np.random.default_rng(SEED)
    m, l, n = SHAPE
    t0 = time.perf_counter()
    ctx = HEContext(CkksEngine(params), verify="error")
    plan = plan_hemm(ctx.eng, m, l, n)
    t_plan = time.perf_counter() - t0
    t0 = time.perf_counter()
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    t_keygen = time.perf_counter() - t0
    A = rng.uniform(-1, 1, (m, l))
    B = rng.uniform(-1, 1, (l, n))
    ctA = encrypt_matrix(ctx.eng, ctx.keys, A, rng)
    ctB = encrypt_matrix(ctx.eng, ctx.keys, B, rng)
    return dict(ctx=ctx, plan=plan, A=A, B=B, ctA=ctA, ctB=ctB,
                t_plan=t_plan, t_keygen=t_keygen)


def run_program(s, warm: bool = True, **compile_kw):
    """Compile, run once cold (and once warm), decrypt; returns the
    record."""
    import jax
    from repro.core.compile import compile_hemm
    from repro.core.hemm import decrypt_matrix

    ctx = s["ctx"]
    t0 = time.perf_counter()
    prog = compile_hemm(ctx, s["plan"], **compile_kw)
    t_compile = time.perf_counter() - t0
    t0 = time.perf_counter()
    ct = prog(s["ctA"], s["ctB"])
    jax.block_until_ready((ct.c0, ct.c1))
    t_first = time.perf_counter() - t0
    t_warm = None
    if warm:
        t0 = time.perf_counter()
        ct = prog(s["ctA"], s["ctB"])
        jax.block_until_ready((ct.c0, ct.c1))
        t_warm = time.perf_counter() - t0
    m, _, n = SHAPE
    C = decrypt_matrix(ctx.eng, ctx.keys, ct, m, n)
    dev = np.abs(C - s["A"] @ s["B"])
    err, rms = float(np.max(dev)), float(np.sqrt(np.mean(dev ** 2)))
    return dict(prog=prog, ct=ct, C=C, err=err, rms=rms, t_compile=t_compile,
                t_first=t_first, t_warm=t_warm)


def n_differ(a, b) -> int:
    """Output coefficients (c0 and c1 limbs) that differ between two runs."""
    return int(np.sum(np.asarray(a.c0) != np.asarray(b.c0))
               + np.sum(np.asarray(a.c1) != np.asarray(b.c1)))


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")


def one_chip(params) -> None:
    from repro.core.hemm import decrypt_matrix

    s = build(params)
    log(f"set-a type-iv {SHAPE}: plan {s['t_plan']:.3f} s, keygen "
        f"{s['t_keygen']:.3f} s (host)")
    m, l, n = SHAPE
    ctx = s["ctx"]
    err_in = float(np.max(np.abs(
        decrypt_matrix(ctx.eng, ctx.keys, s["ctA"], m, l) - s["A"])))
    log(f"fresh encryption max abs error: {err_in!r}")
    r = run_program(s)
    plan = r["prog"].plan
    for name, st in (("step1", plan.step1), ("step2", plan.step2)):
        log(f"{name}: schedule={st.schedule} datapath={st.datapath} "
            f"batch={st.batch} chunk={st.chunk} d_pad={st.d_pad}")
        check(st.schedule == "pallas" and st.datapath == "pallas",
              f"{name} left the fused Pallas path")
    log(f"compile {r['t_compile']:.3f} s (host); first call "
        f"{r['t_first']:.3f} s incl. compilation")
    log(f"warm call {r['t_warm']:.3f} s (set-up figure, not a benchmark)")
    n_kernels = r["prog"].hlo(s["ctA"], s["ctB"]).count("tpu_custom_call")
    log(f"step-2 program: {n_kernels} tpu_custom_call sites")
    log(f"max abs error vs float64 A@B: {r['err']!r} "
        f"(tolerance {TOLERANCE!r}); rms {r['rms']!r}")
    mo = run_program(s, warm=False, schedule="mo")
    log(f"mo (u64 XLA oracle) max abs error: {mo['err']!r}; "
        f"rms {mo['rms']!r}")
    total = 2 * r["ct"].c0.size
    log(f"pallas vs mo: {n_differ(r['ct'], mo['ct'])} of {total} output "
        f"coefficients differ; decrypted outputs differ by at most "
        f"{float(np.max(np.abs(r['C'] - mo['C'])))!r}")
    check(n_kernels > 0, "Step-2 program holds no Pallas kernel")
    check(r["err"] <= TOLERANCE, "error above tolerance")


def four_chip(params) -> None:
    import jax
    from repro.core.compile import HEContext
    from repro.launch.mesh import make_mesh_for

    check(len(jax.devices()) >= 4, "--four-chip needs four devices")
    one = build(params)
    log(f"set-a type-iv {SHAPE}: plan {one['t_plan']:.3f} s, keygen "
        f"{one['t_keygen']:.3f} s (host)")
    ref = run_program(one, warm=False, schedule="pallas")
    log(f"one-chip pallas: max abs error {ref['err']!r}")
    mesh = make_mesh_for(4, model_parallel=2)          # data 2 × model 2
    ctx = one["ctx"]
    four = dict(one, ctx=HEContext(ctx.eng, keys=ctx.keys, mesh=mesh,
                                   verify="error"))
    r = run_program(four, schedule="sharded")
    plan = r["prog"].plan
    log(f"sharded: n_model={plan.step2.n_model} n_ct={plan.step2.n_ct} "
        f"datapath={plan.step2.datapath}; compile {r['t_compile']:.3f} s; "
        f"first call {r['t_first']:.3f} s; warm call {r['t_warm']:.3f} s "
        f"(set-up figure)")
    held = r["prog"].device_bytes()
    log(f"operand bytes held per device: {held}")
    log(f"sharded max abs error vs float64 A@B: {r['err']!r} "
        f"(tolerance {TOLERANCE!r})")
    total = 2 * r["ct"].c0.size
    log(f"sharded vs one-chip pallas: {n_differ(r['ct'], ref['ct'])} of "
        f"{total} output coefficients differ; decrypted outputs differ by "
        f"at most {float(np.max(np.abs(r['C'] - ref['C'])))!r}")
    check(plan.step2.schedule == "sharded" and plan.step2.n_model == 2
          and plan.step2.n_ct == 2, "Step 2 is not the 2×2 sharded program")
    check(len(held) == 4 and min(held.values()) > 0,
          "a device holds no shard of the sharded program's operands")
    check(ref["err"] <= TOLERANCE, "one-chip error above tolerance")
    check(r["err"] <= TOLERANCE, "sharded error above tolerance")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the four-chip sharded phase")
    args = ap.parse_args()

    import repro  # noqa: F401  (x64 for the host-side oracles)
    from repro.core.params import SET_A
    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.enable()}")
    dev = _device()
    log(f"device: {dev['kind']} x{dev['count']} ({dev['platform']})")
    check(dev["platform"] == "tpu", "no TPU: JAX found only "
          f"{dev['platform']} devices")
    params = SET_A.runtime_variant()
    log(f"params: {params.name} N={params.N} L={params.L} k={params.k} "
        f"beta={params.beta}")
    (four_chip if args.four_chip else one_chip)(params)
    print(json.dumps({"ok": True, "device": _device()}), flush=True)


if __name__ == "__main__":
    main()
