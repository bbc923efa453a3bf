"""Benchmark harness — one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived = the table's headline
quantity: counts, MB, speedups, ...). Sections:

  table1   — HE MM operation counts (paper Table I) for the Table III grid
  table2   — parameter sets + §III-B3 cost-model numbers (0.43/3.6 MB, ...)
  eq24     — MO-HLT on-chip requirement + reduction factor (Fig. 2 / Eq. 24)
  fig6     — measured HLT/HE MM latency per compiled schedule: baseline vs
             hoisted vs MO vs fused Pallas programs (CPU, reduced N) + the
             paper's FPGA speedups
  blockmm  — batched block MM (slot-indexed fused pipelines over all
             ciphertext tiles) vs the sequential tile loop
  dist     — schedule="sharded" (limb-sharded shard_map MO-HLT driving the
             fused Pallas kernel per rank) across device counts (forced
             host devices in subprocesses under JAX_PLATFORMS=cpu, the
             process's own devices otherwise): fused vs "sharded_xla" wall
             times, measured-vs-predicted collective bytes, and in-program
             hoist bytes before/after the ct-slot dedup
  serve    — multi-tenant secure serving: cross-request batched (one launch
             per decode step) vs per-request secure-layer calls, operand
             bytes, shared-prompt hoist dedup (BENCH_serve.json)
  chain    — consecutive HE MM chains (compile_hemm_chain): the fully
             encrypted k-hop chain vs the decrypt-between-hops baseline
             (wall time + the decrypt/re-encrypt round-trips it removes),
             per-hop levels and operand bytes (BENCH_chain.json)
  kernels  — Pallas kernel calls (interpret mode) vs jnp oracle
  roofline — §Roofline table from results/dryrun/*.json (if present)

Flags:
  --json [PATH]  also write machine-readable results: hemm/fig6 data to PATH
                 (default BENCH_hemm.json) plus one sibling file per extra
                 section (BENCH_blockmm.json, BENCH_dist.json,
                 BENCH_serve.json) so CI can track each perf trajectory
                 separately
  --smoke        minimal reps / sizes — CI smoke mode

Timing is min-over-reps (after a warmup/compile call): jax's eager dispatch
cache thrashes between interleaved pipelines, so a mean over reps is noisy
while the min is stable (see memory: FAME repo perf facts).
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import numpy as np

# --json collector: section -> {key: value}; filled by the bench functions.
RESULTS: dict = {}

# sections that get their own BENCH_<name>.json next to the --json path
SPLIT_SECTIONS = ("blockmm", "dist", "serve", "chain")

# BENCH_*.json output contract: required keys per structured section.  The
# CI smoke steps write these files and downstream tooling tracks each perf
# trajectory by key, so drift (a renamed or dropped field) must fail the
# run loudly instead of silently breaking the comparison.
BENCH_SCHEMA = {
    "hemm": ("shape", "logN", "hlt_us_per_schedule", "hemm_us_per_schedule",
             "stage_us_per_datapath", "step2_operand_bytes", "step2_plan"),
    "blockmm": ("shape", "loop_us", "batched_us", "step1_operand_bytes",
                "step1_slots", "schedule"),
    "dist": ("batch", "logN", "per_device_count"),
    "serve": ("requests_per_step", "batched_us", "per_request_us",
              "batched_speedup_x", "launches_per_step", "operand_bytes",
              "hoist_dedup_saved_bytes", "program_cache", "session_pool"),
    "chain": ("dims", "depth", "chained_us", "decrypt_hops_us",
              "chained_speedup_x", "decrypts_removed", "hop_levels",
              "hop_bytes", "operand_bytes", "schedules"),
}


def validate_results(results: dict) -> list:
    """Validate the --json collector against BENCH_SCHEMA.

    Structured sections must carry every required key; row-style sections
    (table1, costmodel, fig6, ...) must hold ``us_per_call``/``derived``
    row entries.  Returns human-readable problems (empty == valid)."""
    problems = []
    for section, data in results.items():
        if section in BENCH_SCHEMA:
            missing = [k for k in BENCH_SCHEMA[section] if k not in data]
            if missing:
                problems.append(f"{section}: missing required key(s) "
                                f"{', '.join(missing)}")
            continue
        for name, entry in data.items():
            if not isinstance(entry, dict) or \
                    {"us_per_call", "derived"} - set(entry):
                problems.append(f"{section}/{name}: row entries need "
                                f"us_per_call and derived")
    return problems


def _t(fn, *args, reps=3, **kw):
    """min-over-reps wall time in µs (each rep blocked to completion)."""
    _block(fn(*args, **kw))            # warmup / compile (block: async tail
    best = float("inf")                # must not leak into the first rep)
    out = None
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        _block(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6, out


def _block(x):
    import jax
    jax.block_until_ready(x)


def row(name, us, derived):
    print(f"{name},{us if us is None else round(us, 1)},{derived}",
          flush=True)
    section = name.split("/", 1)[0]
    RESULTS.setdefault(section, {})[name] = {
        "us_per_call": None if us is None else round(us, 1),
        "derived": str(derived)}


def bench_table1():
    from repro.core.costmodel import CostModel
    from repro.core.params import SET_A
    from repro.configs.fame_sets import MM_BENCHMARKS
    cm = CostModel(SET_A)
    for set_name, grid in MM_BENCHMARKS.items():
        for typ, (m, l, n) in grid.items():
            c = cm.table1_counts(m, l, n)["total"]
            row(f"table1/{set_name}/{typ}/{m}-{l}-{n}", None,
                f"Rot={c['Rot']};CMult={c['CMult']};Add={c['Add']};"
                f"Mult={c['Mult']};Depth={c['Depth']}")


def bench_table2_costmodel():
    from repro.core.costmodel import report
    from repro.core.params import SET_A, SET_B, SET_C
    for p in (SET_A, SET_B, SET_C):
        r = report(p, "paper")
        row(f"costmodel/{p.name}/B_ct", None, f"{r['B_ct_MB']:.2f}MB")
        row(f"costmodel/{p.name}/M_hemm", None, f"{r['M_hemm_MB']:.1f}MB")
        row(f"costmodel/{p.name}/M_mo_hlt", None,
            f"{r['M_mo_hlt_MB']:.1f}MB")
        row(f"costmodel/{p.name}/reduction", None,
            f"{r['reduction_x']:.1f}x")


def bench_fig6_schedules(smoke: bool = False):
    """Measured on CPU at reduced N (structure identical to the paper's):
    per-HLT latency for each COMPILED schedule + full HE MM programs, plus
    the Step-2 operand footprint before/after slot dedup."""
    from repro.core.ckks import CkksEngine
    from repro.core.compile import HEContext, compile_hemm, compile_hlt
    from repro.core.hemm import plan_hemm, encrypt_matrix
    from repro.core.params import toy_params

    reps = 1 if smoke else 3
    logN = 7 if smoke else 8
    ctx = HEContext(CkksEngine(
        toy_params(logN=logN, L=4, k=3, beta=2, scale_bits=26)))
    eng = ctx.eng
    rng = np.random.default_rng(0)
    m = l = n = 8                       # Type-IV (square) at reduced scale
    plan = plan_hemm(eng, m, l, n)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    A = rng.uniform(-1, 1, (m, l))
    B = rng.uniform(-1, 1, (l, n))
    ctA = encrypt_matrix(eng, ctx.keys, A, rng)
    ctB = encrypt_matrix(eng, ctx.keys, B, rng)
    ds = plan.ds_sigma

    hlt_us = {}
    for sched, r in (("baseline", 1), ("hoisted", 1), ("mo", reps),
                     ("pallas", reps)):
        run = compile_hlt(ctx, ds, level=ctA.level, schedule=sched)
        hlt_us[sched], _ = _t(lambda run=run: run(ctA), reps=r)
    row("fig6/hlt/baseline", hlt_us["baseline"], f"d={ds.d}")
    for sched in ("hoisted", "mo", "pallas"):
        row(f"fig6/hlt/{sched}", hlt_us[sched],
            f"speedup_vs_baseline={hlt_us['baseline'] / hlt_us[sched]:.2f}x")

    # per-stage base-change timings, fused Pallas vs XLA lowering (§7 knob):
    # hoist = Decomp→iNTT→BaseConv→NTT, moddown = the merged ModDown+Rescale
    # tail.  (On CPU the fused path runs in the Pallas interpreter, so the
    # trajectory — not the ratio — is the signal; on TPU this measures the
    # actual datapath.)
    from repro.core import hlt as hlt_mod
    acc = hlt_mod.hoist(eng, ctA, datapath="xla").c0_ext
    stage_us = {}
    for dp in ("pallas", "xla"):
        us_h, _ = _t(lambda dp=dp: (lambda h: (h.digits, h.c0_ext, h.c1_ext))(
            hlt_mod.hoist(eng, ctA, datapath=dp)), reps=reps)
        us_m, _ = _t(lambda dp=dp: eng._mod_down_eval(
            acc, ctA.level, drop_last=True, datapath=dp), reps=reps)
        stage_us[dp] = {"hoist": round(us_h, 1), "moddown": round(us_m, 1)}
    for st in ("hoist", "moddown"):
        row(f"fig6/stage/{st}", stage_us["pallas"][st],
            f"xla_us={stage_us['xla'][st]};"
            f"fused_vs_xla={stage_us['xla'][st] / stage_us['pallas'][st]:.2f}x")

    prog_mo = compile_hemm(ctx, plan, schedule="mo")
    prog_pl = compile_hemm(ctx, plan, schedule="pallas")
    us_mm, _ = _t(lambda: prog_mo(ctA, ctB), reps=1)
    row("fig6/hemm/8-8-8/mo", us_mm, "depth=3")
    us_mmp, _ = _t(lambda: prog_pl(ctA, ctB), reps=1)
    row("fig6/hemm/8-8-8/pallas", us_mmp,
        f"depth=3;batched_step2;vs_mo={us_mm / us_mmp:.2f}x")
    row("fig6/paper/avg_speedup", None, "221x (FPGA, paper Fig. 6)")
    row("fig6/paper/max_speedup", None, "1337x (160-160-160 Set-C)")

    # operand footprint of the compiled Step-2 (2·l HLTs): key/diag tensors
    # deduped to unique slots, hoisting digits stored 2× (A0/B0) instead of
    # 2·l× — now straight off the plan's ct-slot accounting.
    s2 = prog_pl.plan.step2
    hoist_dedup, hoist_naive = s2.hoist_bytes, s2.hoist_bytes_naive
    row("fig6/operands/step2_diag", None,
        f"dedup_MB={s2.operand_bytes / 2**20:.3f};"
        f"naive_MB={s2.operand_bytes_naive / 2**20:.3f}")
    row("fig6/operands/step2_hoist", None,
        f"dedup_MB={hoist_dedup / 2**20:.3f};"
        f"naive_MB={hoist_naive / 2**20:.3f};x={hoist_naive / hoist_dedup:.1f}")
    RESULTS["hemm"] = {
        "shape": [m, l, n], "logN": logN,
        "hlt_us_per_schedule": {k: round(v, 1) for k, v in hlt_us.items()},
        "hemm_us_per_schedule": {"mo": round(us_mm, 1),
                                 "pallas": round(us_mmp, 1)},
        "stage_us_per_datapath": stage_us,
        "step2_operand_bytes": {
            "diag_dedup": s2.operand_bytes,
            "diag_naive": s2.operand_bytes_naive,
            "hoist_dedup": hoist_dedup, "hoist_naive": hoist_naive},
        "step2_plan": {"batch": s2.batch, "n_diag_slots": s2.n_diag_slots,
                       "chunk": s2.chunk, "d_pad": s2.d_pad,
                       "schedule": s2.schedule, "datapath": s2.datapath},
    }


def bench_blockmm(smoke: bool = False):
    """Block MM across ciphertext tiles (paper §VI-D / abstract's large-scale
    consecutive HE MM): sequential per-tile-pair hemm-program loop vs the
    slot-indexed batched pipelines (cost-model-selected schedule)."""
    from repro.core.compile import compile_hlt
    from repro.core.params import toy_params
    from repro.secure import SecureMatmulEngine
    rng = np.random.default_rng(0)
    engine = SecureMatmulEngine(toy_params(logN=6, L=4, k=3, beta=2), tile=4)
    # smoke: 2+2 tiles instead of 4+4 — same dedup story, ~half the wall time
    ma, nb = ((4, 4) if smoke else (6, 7))
    A = rng.uniform(-1, 1, (ma, 5))
    B = rng.uniform(-1, 1, (5, nb))
    engine.keygen(rng)
    At = engine.encrypt_tiles(A, rng)
    Bt = engine.encrypt_tiles(B, rng)
    shape = f"{A.shape[0]}x{A.shape[1]}@{B.shape[1]}/tile{engine.tile}"
    us_loop, _ = _t(lambda: engine.matmul_encrypted(At, Bt, batched=False),
                    reps=1)
    us_bat, _ = _t(lambda: engine.matmul_encrypted(At, Bt, batched=True),
                   reps=1)
    row(f"blockmm/{shape}/loop", us_loop, "sequential tile loop")
    row(f"blockmm/{shape}/batched", us_bat,
        f"speedup_vs_loop={us_loop / us_bat:.2f}x")
    # Step-1 operand dedup across the tile grid: σ/τ tensors stored once
    # each (2 slots), not once per tile (memoized compile — same object).
    plan = engine._plan
    nA, nB = len(At) * len(At[0]), len(Bt) * len(Bt[0])
    step1 = compile_hlt(
        engine.ctx, [plan.ds_sigma] * nA + [plan.ds_tau] * nB,
        level=At[0][0].level, schedule=engine.schedule,
        rotation_chunk=engine.rotation_chunk)
    s1 = step1.plan
    row(f"blockmm/{shape}/step1_operands", None,
        f"slots={s1.n_diag_slots}/{s1.batch};"
        f"dedup_MB={s1.operand_bytes / 2**20:.3f};"
        f"naive_MB={s1.operand_bytes_naive / 2**20:.3f};"
        f"x={s1.dedup_factor:.1f}")
    RESULTS["blockmm"] = {
        "shape": shape, "loop_us": round(us_loop, 1),
        "batched_us": round(us_bat, 1),
        "step1_operand_bytes": {"dedup": s1.operand_bytes,
                                "naive": s1.operand_bytes_naive},
        "step1_slots": {"unique": s1.n_diag_slots, "batch": s1.batch},
        "schedule": engine.schedule,
    }


def _dist_case(dev: int, logn: int, reps: int, batch: int) -> dict:
    """One device count of bench_dist, on the first ``dev`` devices."""
    import jax
    from repro.core.ckks import CkksEngine
    from repro.core.compile import HEContext, compile_hlt
    from repro.core.hemm import plan_hemm, encrypt_matrix
    from repro.core.params import toy_params
    from repro.launch.mesh import make_mesh_for
    from repro.distributed.hlo_analysis import collective_stats

    params = toy_params(logN=logn, L=4, k=3, beta=2)
    mesh = make_mesh_for(dev, model_parallel=dev) if dev > 1 else None
    ctx = HEContext(CkksEngine(params), mesh=mesh)
    rng = np.random.default_rng(0)
    plan = plan_hemm(ctx.eng, 4, 3, 5)
    ctx.keygen(rng, rot_steps=plan.rot_steps)
    cts = [encrypt_matrix(ctx.eng, ctx.keys, rng.uniform(-1, 1, (4, 3)), rng)
           for _ in range(batch)]

    def timed(fn):
        out = fn()                               # warmup / compile
        jax.block_until_ready([c.c0 for c in out])
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            out = fn()
            jax.block_until_ready([c.c0 for c in out])
            best = min(best, time.perf_counter() - t0)
        return best * 1e6

    level = cts[0].level
    run = compile_hlt(ctx, [plan.ds_sigma] * batch, level=level,
                      schedule="sharded")
    runx = compile_hlt(ctx, [plan.ds_sigma] * batch, level=level,
                       schedule="sharded_xla")
    st = collective_stats(run.hlo(cts))
    # hoist-dedup story: the hemm Step-2 aliasing pattern (2 unique inputs
    # across the batch) — bytes before/after the ct-slot dedup, from the plans
    hint = tuple(b % 2 for b in range(batch))
    aliased = compile_hlt(ctx, [plan.ds_sigma] * batch, level=level,
                          schedule="sharded", ct_slots=hint)
    res = dict(devices=dev, n_model=ctx.n_model, n_ct=ctx.n_ct,
               sharded_us=round(timed(lambda: run(cts)), 1),
               sharded_xla_us=round(timed(lambda: runx(cts)), 1),
               predicted_collective_bytes=run.plan.collective_bytes,
               measured_collective_bytes=st.total_bytes,
               collective_count=st.count,
               hoist_bytes_dedup=aliased.plan.hoist_bytes,
               hoist_bytes_naive=aliased.plan.hoist_bytes_naive,
               n_ct_slots=aliased.plan.n_ct_slots)
    if dev == 1:
        mo = compile_hlt(ctx, [plan.ds_sigma] * batch, level=level,
                         schedule="mo")
        res["mo_us"] = round(timed(lambda: mo(cts)), 1)
    return res


# forced-CPU child for bench_dist: XLA_FLAGS must be set BEFORE jax
# initializes, so on the CPU every device count runs in a fresh process.
_DIST_CHILD = """
import json, sys
sys.path.insert(0, {bench!r})
import repro
import run
print(json.dumps(run._dist_case({dev}, {logn}, {reps}, {batch})))
"""


def bench_dist(smoke: bool = False):
    """schedule="sharded" (limb-sharded shard_map MO-HLT through the FUSED
    Pallas datapath, core/hlt_dist.py) across device counts (forced host
    devices under JAX_PLATFORMS=cpu, the real devices otherwise):
    per-count wall time of one batched HLT for the fused datapath vs the
    "sharded_xla" pre-fusion baseline, the plan's PREDICTED collective bytes
    vs the bytes MEASURED in the compiled HLO
    (distributed/hlo_analysis.collective_stats), and the in-program hoist
    bytes before/after the ct-slot dedup on the hemm-Step-2 aliasing
    pattern.  Measured counts full all-reduce operand traffic; predicted is
    the ring-adjusted per-device estimate — same order, different
    convention.  (Interpret-mode caveat: on CPU the fused kernel runs in the
    Pallas interpreter, so fused-vs-XLA wall times track lowering overhead,
    not TPU datapath reuse — the trajectory, not the speedup, is the
    signal.)"""
    counts = (1, 4) if smoke else (1, 2, 4)
    reps = 1 if smoke else 3
    batch, logn = 4, 6
    here = os.path.dirname(os.path.abspath(__file__))
    src = os.path.join(here, "..", "src")
    # Forced host devices only where JAX is held to the CPU. Anywhere else
    # the device counts run in this process on jax.devices(): a chip belongs
    # to one process, so a child started after this one touched JAX could
    # not reach it.
    forced_cpu = os.environ.get("JAX_PLATFORMS") == "cpu"
    if not forced_cpu:
        import jax
        counts = tuple(c for c in counts if c <= len(jax.devices()))
    per_count = {}
    for dev in counts:
        if forced_cpu:
            code = _DIST_CHILD.format(bench=here, dev=dev, logn=logn,
                                      reps=reps, batch=batch)
            env = dict(os.environ, XLA_FLAGS=(
                f"--xla_force_host_platform_device_count={dev}"))
            env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
            r = subprocess.run([sys.executable, "-c", code], env=env,
                               capture_output=True, text=True, timeout=1800)
            assert r.returncode == 0, r.stderr[-3000:]
            res = json.loads(r.stdout.strip().splitlines()[-1])
        else:
            res = _dist_case(dev, logn, reps, batch)
        per_count[str(dev)] = res
        row(f"dist/devices={dev}/sharded_hlt", res["sharded_us"],
            f"coll_pred_B={res['predicted_collective_bytes']};"
            f"coll_meas_B={res['measured_collective_bytes']};"
            f"n_model={res['n_model']}")
        row(f"dist/devices={dev}/sharded_xla_hlt", res["sharded_xla_us"],
            f"fused_vs_xla={res['sharded_xla_us'] / res['sharded_us']:.2f}x")
        row(f"dist/devices={dev}/step2_hoist", None,
            f"dedup_B={res['hoist_bytes_dedup']};"
            f"naive_B={res['hoist_bytes_naive']};"
            f"n_ct_slots={res['n_ct_slots']}")
        if "mo_us" in res:
            row(f"dist/devices={dev}/mo_hlt", res["mo_us"],
                "single-device reference")
    RESULTS["dist"] = {"batch": batch, "logN": logn, "per_device_count":
                       per_count}


def bench_serve(smoke: bool = False):
    """Multi-tenant secure serving (serve/sessions.py + serve/he_batcher.py):
    R in-flight requests' secure-layer calls per decode step, cross-request
    batched (ONE BlockMMProgram launch per step) vs per-request launches
    (the pre-subsystem behavior), plus the arena-deduped operand bytes the
    one-launch program streams vs the per-request naive bound and the
    hoisting products skipped by shared-prompt aliasing."""
    from repro.core.params import toy_params
    from repro.serve.he_batcher import CrossRequestHEBatcher, SecureCall
    from repro.serve.sessions import HEProgramCache, SessionPool

    reps = 1 if smoke else 3
    R = 3 if smoke else 6               # in-flight requests per decode step
    d_in, d_out = 8, 4
    rng = np.random.default_rng(0)
    pool = SessionPool(toy_params(logN=6, L=4, k=3, beta=2), tile=4)
    pool.attach_weights({0: rng.standard_normal((d_in, d_out)) * 0.4})
    # two of the R requests share a prompt -> identical activation rows
    xs = [rng.standard_normal(d_in) for _ in range(R - 1)]
    xs.append(xs[0].copy())

    def one_step(bat):
        for rid, x in enumerate(xs):
            bat.submit(SecureCall(rid, 0, x))
        return bat.flush()

    bat = CrossRequestHEBatcher(pool, rng=np.random.default_rng(1))
    us_bat, _ = _t(lambda: one_step(bat), reps=reps)
    per = CrossRequestHEBatcher(pool, cache=HEProgramCache(),
                                rng=np.random.default_rng(1),
                                batch_requests=False)
    us_per, _ = _t(lambda: one_step(per), reps=reps)

    s_bat, s_per = bat.steps[-1], per.steps[-1]
    row(f"serve/{R}req/batched", us_bat,
        f"launches_per_step={s_bat.program_launches};"
        f"hlt_launches={s_bat.hlt_launches}")
    row(f"serve/{R}req/per_request", us_per,
        f"launches_per_step={s_per.program_launches};"
        f"batched_speedup={us_per / us_bat:.2f}x")
    # operand bytes of the one-launch program (arena-deduped vs naive) and
    # the hoist bytes the shared-prompt aliasing saved this step
    sess = pool.session("default", np.random.default_rng(2))
    prog = bat.cache.get(sess, sess.engine._plan, (R, 2, 1),
                         level=pool.params.L, schedule=sess.engine.schedule)
    bp = prog.plan
    row(f"serve/{R}req/operand_bytes", None,
        f"dedup_B={bp.operand_bytes};naive_B={bp.operand_bytes_naive};"
        f"x={bp.operand_bytes_naive / max(1, bp.operand_bytes):.1f}")
    row(f"serve/{R}req/hoist_dedup", None,
        f"saved_B={s_bat.amortization['hoist_dedup_saved_bytes']};"
        f"uniq_tiles={s_bat.n_uniq_tiles}/{s_bat.n_tiles}")
    RESULTS["serve"] = {
        "requests_per_step": R,
        "batched_us": round(us_bat, 1),
        "per_request_us": round(us_per, 1),
        "batched_speedup_x": round(us_per / us_bat, 2),
        "launches_per_step": {"batched": s_bat.program_launches,
                              "per_request": s_per.program_launches},
        "operand_bytes": {"dedup": bp.operand_bytes,
                          "naive": bp.operand_bytes_naive},
        "hoist_dedup_saved_bytes":
            s_bat.amortization["hoist_dedup_saved_bytes"],
        "program_cache": bat.cache.report(),
        "session_pool": pool.report(),
    }


def bench_chain(smoke: bool = False):
    """Consecutive HE MM chains (core/compile.py compile_hemm_chain): the
    fully encrypted k-hop chain Y = X·W1·…·Wk as ONE compiled program vs
    the decrypt-between-hops baseline (one top-level hemm per hop with a
    decrypt + two re-encrypts in between — what stacked SecureLinear
    layers used to do).  The chain removes k-1 client round-trips AND runs
    every hop at a descending level (cheaper limbs per hop), at the price
    of needing 3·k levels of modulus chain (see
    configs/fame_sets.py FAME_CHAIN_SETS for the β sizing)."""
    from repro.configs.fame_sets import FAME_CHAIN_SETS
    from repro.core.ckks import CkksEngine
    from repro.core.compile import HEContext, compile_hemm,\
        compile_hemm_chain
    from repro.core.hemm import (decrypt_matrix, encrypt_matrix,
                                 plan_hemm_chain)

    reps = 1 if smoke else 3
    depth = 2 if smoke else 3
    rng = np.random.default_rng(0)
    ctx = HEContext(CkksEngine(FAME_CHAIN_SETS["fame-s-chain"]))
    eng = ctx.eng
    dims = (3,) * (depth + 2)
    chain = plan_hemm_chain(eng, dims)
    ctx.keygen(rng, rot_steps=chain.rot_steps)
    prog = compile_hemm_chain(ctx, chain)
    X = rng.uniform(-0.5, 0.5, (dims[0], dims[1]))
    Ws = [rng.uniform(-0.5, 0.5, (dims[h + 1], dims[h + 2]))
          for h in range(depth)]
    ctX = encrypt_matrix(eng, ctx.keys, X, rng)
    w_cts = prog.encrypt_weights(Ws, rng)
    us_chain, out = _t(lambda: prog(ctX, w_cts), reps=reps)
    _block(out)

    # baseline: decrypt/re-encrypt between hops, every hop at top level
    base_progs = [compile_hemm(ctx, hp) for hp in chain.hops]

    def decrypt_between_hops():
        y = X
        for bp, hp, W in zip(base_progs, chain.hops, Ws):
            cty = encrypt_matrix(eng, ctx.keys, y, rng)
            ctw = encrypt_matrix(eng, ctx.keys, W, rng)
            y = decrypt_matrix(eng, ctx.keys, bp(cty, ctw), hp.m, hp.n)
        return y

    us_hops, y = _t(decrypt_between_hops, reps=reps)
    Y = decrypt_matrix(eng, ctx.keys, out, dims[0], dims[-1])
    assert np.abs(Y - y).max() < 5e-4   # the two pipelines must agree

    name = "x".join(str(d) for d in dims)
    row(f"chain/{name}/chained", us_chain,
        f"depth={depth};hop_levels={list(prog.plan.hop_levels)};"
        f"schedules={list(prog.plan.schedules)}")
    row(f"chain/{name}/decrypt_between_hops", us_hops,
        f"chained_speedup={us_hops / us_chain:.2f}x;"
        f"decrypts_removed={depth - 1};reencrypts_removed={2 * depth - 1}")
    row(f"chain/{name}/operands", None,
        f"per_hop_B={list(prog.plan.hop_bytes)};"
        f"total_B={prog.plan.operand_bytes}")
    RESULTS["chain"] = {
        "dims": list(dims), "depth": depth,
        "chained_us": round(us_chain, 1),
        "decrypt_hops_us": round(us_hops, 1),
        "chained_speedup_x": round(us_hops / us_chain, 2),
        "decrypts_removed": depth - 1,
        "hop_levels": list(prog.plan.hop_levels),
        "hop_bytes": list(prog.plan.hop_bytes),
        "operand_bytes": prog.plan.operand_bytes,
        "schedules": list(prog.plan.schedules),
    }


def bench_kernels():
    import jax.numpy as jnp
    from repro.core.params import toy_params, get_context
    from repro.kernels import ops, ref
    ctx = get_context(toy_params(logN=10, L=3, k=2, beta=2))
    rng = np.random.default_rng(0)
    p = ctx.params
    M = p.num_total
    qs = np.asarray(ctx.moduli_host, np.uint64)[:, None]
    x = rng.integers(0, qs, (M, p.N)).astype(np.uint32)
    y = rng.integers(0, qs, (M, p.N)).astype(np.uint32)
    import jax
    xj, yj = jnp.asarray(x), jnp.asarray(y)
    us, _ = _t(ops.modmul, xj, yj, ctx.moduli_u32, ctx.qneg_inv)
    row("kernels/modmul", us, f"{M}x{p.N} u32")
    us_r, _ = _t(ref.modmul_ref, xj, yj, ctx.moduli_u32, ctx.qneg_inv)
    row("kernels/modmul_ref", us_r, "oracle")
    xb = jnp.asarray(x[None])
    us, _ = _t(ops.ntt, xb, ctx.psi_brv_mont, ctx.moduli_u32, ctx.qneg_inv)
    row("kernels/ntt", us, f"N={p.N} M={M}")


def bench_roofline():
    import glob
    import json
    import os
    base = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
    files = sorted(glob.glob(os.path.join(base, "*__pod.json")))
    for f in files[:50]:
        r = json.load(open(f))
        if not r.get("ok") or "roofline" in r and r.get("skipped"):
            continue
        t = r.get("roofline")
        if not t:
            continue
        dom = r.get("dominant", "?")
        row(f"roofline/{r['arch']}/{r['shape']}", None,
            f"compute={t['compute_s']:.2e}s;memory={t['memory_s']:.2e}s;"
            f"collective={t['collective_s']:.2e}s;dom={dom}")


def main() -> None:
    import inspect

    import repro  # noqa: F401
    from repro.launch import compile_cache
    compile_cache.enable()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("section", nargs="?", default=None,
                    help="run only sections whose name contains this")
    ap.add_argument("--json", nargs="?", const="BENCH_hemm.json", default=None,
                    metavar="PATH", help="write machine-readable results")
    ap.add_argument("--smoke", action="store_true",
                    help="minimal reps / sizes (CI smoke mode)")
    args = ap.parse_args()
    print("name,us_per_call,derived")
    sections = [bench_table1, bench_table2_costmodel, bench_fig6_schedules,
                bench_blockmm, bench_dist, bench_serve, bench_chain,
                bench_kernels, bench_roofline]
    for fn in sections:
        if args.section and args.section not in fn.__name__:
            continue
        if "smoke" in inspect.signature(fn).parameters:
            fn(smoke=args.smoke)
        else:
            fn()
    if args.json:
        problems = validate_results(RESULTS)
        if problems:
            for p in problems:
                print(f"# BENCH schema drift: {p}", file=sys.stderr)
            sys.exit(1)
        split = {s: RESULTS.pop(s) for s in SPLIT_SECTIONS if s in RESULTS}
        if RESULTS:
            with open(args.json, "w") as f:
                json.dump(RESULTS, f, indent=2, sort_keys=True)
            print(f"# wrote {args.json}", flush=True)
        base = os.path.dirname(os.path.abspath(args.json))
        for s, data in split.items():
            path = os.path.join(base, f"BENCH_{s}.json")
            with open(path, "w") as f:
                json.dump(data, f, indent=2, sort_keys=True)
            print(f"# wrote {path}", flush=True)


if __name__ == "__main__":
    main()
