import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^^ MUST precede any jax-importing import: jax locks device count on init.
"""Multi-pod dry-run: lower + compile every (architecture × input shape) on
the production meshes, and extract the roofline terms from the compiled
artifact (no device allocation — inputs are ShapeDtypeStructs).

  PYTHONPATH=src python -m repro.launch.dryrun --arch internlm2-1.8b \\
      --shape train_4k --mesh pod
  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both
  PYTHONPATH=src python -m repro.launch.dryrun --he set-b --mesh pod

Results land in results/dryrun/<arch>__<shape>__<mesh>.json.
"""
import argparse
import functools
import json
import time
import traceback

import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401  (x64 flag)
from repro.configs import registry
from repro.configs.registry import SHAPES, cell_enabled
from repro.distributed import hlo_analysis, hlo_cost
from repro.distributed.sharding import make_rules, set_rules, get_rules
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf
from repro.train.train_step import (TrainConfig, abstract_train_state,
                                    param_shardings, train_step)
from repro.serve.engine import (cache_shardings, serve_decode_step,
                                serve_prefill_step)

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                           "results", "dryrun")


def input_specs(arch: str, shape: str) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    cfg = registry.get_config(arch)
    sh = SHAPES[shape]
    B, S = sh["batch"], sh["seq"]
    f = jnp.float32
    i = jnp.int32
    sds = jax.ShapeDtypeStruct
    if sh["step"] == "train":
        specs = {"targets": sds((B, S), i)}
        if cfg.family == "audio":
            specs["embeds"] = sds((B, S, cfg.d_model), f)
        else:
            specs["tokens"] = sds((B, S), i)
        if cfg.family == "vlm":
            specs["frontend"] = sds(
                (B, cfg.frontend_tokens, cfg.frontend_dim or cfg.d_model),
                jnp.bfloat16)
        return specs
    if sh["step"] == "prefill":
        specs = {"tokens": sds((B, S), i)}
        if cfg.family == "audio":
            specs = {"embeds": sds((B, S, cfg.d_model), jnp.bfloat16)}
        return specs
    # decode: one new token (or frame embedding) against a seq_len KV cache
    if cfg.family == "audio":
        return {"token": sds((B, 1, cfg.d_model), jnp.bfloat16)}
    return {"token": sds((B, 1), i)}


def _abstract_cache(cfg, B, S):
    return jax.eval_shape(lambda: tf.init_cache(cfg, B, S))


def run_cell(arch: str, shape: str, mesh_kind: str, microbatches: int = 1,
             overrides: dict | None = None, seq_shard_kv: bool = False) -> dict:
    """Lower + compile one cell; return the §Dry-run/§Roofline record."""
    cfg = registry.get_config(arch)
    if overrides:
        import dataclasses
        cfg = dataclasses.replace(cfg, **overrides)
    sh = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rules = make_rules(mesh)
    set_rules(rules)
    chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))

    t0 = time.time()
    with mesh:
        if sh["step"] == "train":
            tcfg = TrainConfig(microbatches=microbatches)
            state_shapes = abstract_train_state(cfg, tcfg)
            state_sh = param_shardings(cfg, state_shapes, rules)
            batch_specs = input_specs(arch, shape)
            batch_sh = {k: _batch_sharding(rules, v)
                        for k, v in batch_specs.items()}
            fn = functools.partial(train_step, cfg, tcfg)
            lowered = jax.jit(fn, in_shardings=(state_sh, batch_sh),
                              out_shardings=(state_sh, None),
                              donate_argnums=(0,)).lower(
                                  state_shapes, batch_specs)
        elif sh["step"] == "prefill":
            params_shapes = jax.eval_shape(lambda: tf.init_params(
                cfg, jax.random.PRNGKey(0)))
            p_sh = param_shardings(cfg, params_shapes, rules)
            cache_shapes = _abstract_cache(cfg, sh["batch"], sh["seq"])
            c_sh = cache_shardings(rules, cache_shapes)
            specs = input_specs(arch, shape)
            tok = specs.get("tokens", specs.get("embeds"))
            fn = functools.partial(serve_prefill_step, cfg)
            lowered = jax.jit(
                fn, in_shardings=(p_sh, _batch_sharding(rules, tok), c_sh),
                out_shardings=(None, c_sh)).lower(
                    params_shapes, tok, cache_shapes)
        else:  # decode
            params_shapes = jax.eval_shape(lambda: tf.init_params(
                cfg, jax.random.PRNGKey(0)))
            p_sh = param_shardings(cfg, params_shapes, rules)
            cache_shapes = _abstract_cache(cfg, sh["batch"], sh["seq"])
            c_sh = cache_shardings(rules, cache_shapes,
                                   seq_shard_kv=seq_shard_kv)
            tok = input_specs(arch, shape)["token"]
            fn = functools.partial(serve_decode_step, cfg)
            lowered = jax.jit(
                fn, in_shardings=(p_sh, _batch_sharding(rules, tok), c_sh,
                                  None),
                out_shardings=(None, c_sh),
                donate_argnums=(2,)).lower(
                    params_shapes, tok, cache_shapes,
                    jax.ShapeDtypeStruct((), jnp.int32))
        compiled = lowered.compile()
    t1 = time.time()

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_text = compiled.as_text()
    lc = hlo_cost.analyze(hlo_text)           # loop-aware (×trip counts)
    # analyze() works on the per-device SPMD module: totals = per_device×chips
    flops = lc.flops * chips
    hbm_bytes = lc.bytes_accessed * chips
    coll_bytes = lc.collective_bytes * chips
    terms = hlo_analysis.roofline_terms(flops, hbm_bytes, coll_bytes, chips)
    n_params = registry.get_config(arch).param_count()
    tokens = sh["batch"] * (sh["seq"] if sh["step"] == "train" else
                            (sh["seq"] if sh["step"] == "prefill" else 1))
    mult = 6.0 if sh["step"] == "train" else 2.0
    act_frac = _active_frac(registry.get_config(arch))
    model_flops = mult * n_params * act_frac * tokens
    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_kind, "chips": chips,
        "step": sh["step"], "ok": True,
        "compile_s": round(t1 - t0, 2),
        "flops_total": flops,
        "hbm_bytes_total": hbm_bytes,
        "collective_bytes_total": int(coll_bytes),
        "collectives_by_op": {k: v * chips for k, v in
                              lc.collectives_by_op.items()},
        "raw_cost_analysis": {"flops": float(cost.get("flops", 0.0)),
                              "bytes": float(cost.get("bytes accessed", 0.0))},
        "trip_counts": {k: v for k, v in list(lc.trip_counts.items())[:8]},
        "roofline": terms,
        "dominant": hlo_analysis.dominant_term(terms),
        "model_flops": model_flops,
        "useful_flops_ratio": model_flops / flops if flops else None,
        "memory_analysis": _mem_dict(mem),
        "model_params": n_params,
    }
    return rec


def _active_frac(cfg) -> float:
    """Active-parameter fraction for MoE (MODEL_FLOPS uses 6·N_active·D)."""
    if not cfg.num_experts:
        return 1.0
    total = cfg.param_count()
    import dataclasses
    dense_like = dataclasses.replace(
        cfg, num_experts=0, d_ff=cfg.d_ff * cfg.experts_per_token)
    return dense_like.param_count() / total


def _batch_sharding(rules, spec):
    """Batch-dim sharding, replicating when the dim doesn't divide DP."""
    from repro.distributed.sharding import sanitize_spec
    axes = ("batch",) + (None,) * (spec.ndim - 1)
    return rules.sharding(*sanitize_spec(rules, axes, spec.shape))


def _mem_dict(mem) -> dict:
    keys = ["argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "generated_code_size_in_bytes",
            "alias_size_in_bytes"]
    out = {}
    for k in keys:
        try:
            out[k] = int(getattr(mem, k))
        except Exception:
            pass
    return out


def run_he_cell(set_name: str, mesh_kind: str, unroll: int = 1) -> dict:
    """Dry-run the paper's own workload: one MO-HLT fused step (Algorithm 3
    body over all limbs) at full Set-B/C size, limb-parallel over 'model' and
    ciphertext-batch over 'data'. Uses ShapeDtypeStructs only."""
    from repro.core.params import PAPER_SETS
    from repro.core import hlt_dist
    p = PAPER_SETS[set_name]
    mesh = make_production_mesh(multi_pod=(mesh_kind == "multipod"))
    rules = make_rules(mesh)
    set_rules(rules)
    chips = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    t0 = time.time()
    with mesh:
        lowered = hlt_dist.lower_mo_hlt_spmd(p, mesh, rules, d=127,
                                             unroll=unroll)
        compiled = lowered.compile()
    t1 = time.time()
    lc = hlo_cost.analyze(compiled.as_text())
    # integer workload: no dots — VPU elementwise op-elements are the compute
    flops = lc.int_elem_ops * chips
    hbm = lc.bytes_accessed * chips
    coll_bytes = lc.collective_bytes * chips
    terms = hlo_analysis.roofline_terms(flops, hbm, coll_bytes, chips,
                                        peak_flops=hlo_analysis.HW["vpu_u32_ops"])
    return {"arch": f"he-mm-{set_name}", "shape": "mo-hlt-d127",
            "mesh": mesh_kind, "chips": chips, "ok": True,
            "compile_s": round(t1 - t0, 2), "flops_total": flops,
            "hbm_bytes_total": hbm,
            "collective_bytes_total": int(coll_bytes),
            "collectives_by_op": {k: v * chips for k, v in
                                  lc.collectives_by_op.items()},
            "roofline": terms,
            "dominant": hlo_analysis.dominant_term(terms),
            "memory_analysis": _mem_dict(compiled.memory_analysis())}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--he", help="HE set name (set-a/set-b/set-c)")
    ap.add_argument("--mesh", default="pod", choices=["pod", "multipod", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--he-unroll", type=int, default=1)
    ap.add_argument("--opt-cache", action="store_true",
                    help="seq-shard KV caches (decode §Perf variant)")
    ap.add_argument("--suffix", default="", help="result filename suffix")
    ap.add_argument("--out", default=RESULTS_DIR)
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)
    meshes = ["pod", "multipod"] if args.mesh == "both" else [args.mesh]

    cells = []
    if args.he:
        cells = [("he", args.he, None)]
    elif args.all:
        cells = [("lm", a, s) for (a, s) in registry.all_cells()]
    else:
        cells = [("lm", args.arch, args.shape)]

    for kind, a, s in cells:
        for mk in meshes:
            name = f"{a}__{s or 'he'}__{mk}{args.suffix}"
            path = os.path.join(args.out, name + ".json")
            try:
                if kind == "he":
                    rec = run_he_cell(a, mk, unroll=args.he_unroll)
                else:
                    if not cell_enabled(a, s):
                        rec = {"arch": a, "shape": s, "mesh": mk,
                               "ok": True, "skipped":
                               "full-attention arch: long_500k requires "
                               "sub-quadratic attention (DESIGN.md §4)"}
                    else:
                        rec = run_cell(a, s, mk,
                                       microbatches=args.microbatches,
                                       seq_shard_kv=args.opt_cache)
            except Exception as e:  # noqa: BLE001 — record failures as bugs
                rec = {"arch": a, "shape": s, "mesh": mk, "ok": False,
                       "error": repr(e),
                       "traceback": traceback.format_exc()[-3000:]}
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            status = "OK " if rec.get("ok") else "FAIL"
            extra = ("skip: " + rec["skipped"][:40]) if "skipped" in rec else \
                (f"dom={rec.get('dominant', '?')} "
                 f"compile={rec.get('compile_s', '?')}s"
                 if rec.get("ok") else rec.get("error", "")[:80])
            print(f"[{status}] {name}: {extra}", flush=True)


if __name__ == "__main__":
    main()
