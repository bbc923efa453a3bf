"""Production training launcher: mesh + sharded train step + data + fault
tolerance. On a real fleet this runs once per host (jax.distributed
initializes from TPU_WORKER_* env); on this container it exercises the same
code path on host devices.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \\
        --smoke --steps 20 --dp 2 --tp 2
"""
import argparse
import functools
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

import repro  # noqa: F401
from repro.checkpoint import checkpoint as ckpt
from repro.configs import get_config, get_smoke_config
from repro.data.pipeline import DataConfig, PrefetchLoader
from repro.distributed.fault import FaultConfig, StragglerDetector
from repro.distributed.sharding import make_rules, set_rules
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh_for, make_production_mesh
from repro.train.optimizer import OptConfig
from repro.train.train_step import (TrainConfig, init_train_state,
                                    param_shardings, train_step)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-runnable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--production-mesh", action="store_true",
                    help="use the 16x16 (or 2x16x16) production mesh")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if args.production_mesh:
        mesh = make_production_mesh(multi_pod=args.multi_pod)
    else:
        mesh = make_mesh_for(args.dp * args.tp, model_parallel=args.tp)
    rules = make_rules(mesh)
    set_rules(rules)
    tcfg = TrainConfig(
        microbatches=args.microbatches,
        opt=OptConfig(lr=1e-3, warmup_steps=20, total_steps=args.steps,
                      compress_grads=args.compress_grads))
    dcfg = DataConfig(global_batch=args.global_batch, seq_len=args.seq,
                      num_hosts=jax.process_count(),
                      host_id=jax.process_index())

    with mesh:
        state = init_train_state(cfg, tcfg, jax.random.PRNGKey(0))
        st_sh = param_shardings(cfg, jax.eval_shape(lambda: state), rules)
        state = jax.device_put(state, st_sh)
        start = 0
        if ckpt.latest_step(args.ckpt_dir) is not None:
            state, meta = ckpt.restore(
                args.ckpt_dir, jax.eval_shape(lambda: state), shardings=st_sh)
            start = meta["step"]
            print(f"[train] elastic resume from step {start}")
        step_fn = jax.jit(functools.partial(train_step, cfg, tcfg),
                          in_shardings=(st_sh, None),
                          out_shardings=(st_sh, None), donate_argnums=(0,))
        loader = PrefetchLoader(cfg, dcfg, start_step=start)
        saver = ckpt.AsyncCheckpointer(args.ckpt_dir)
        straggle = StragglerDetector(FaultConfig())
        for step, batch in loader:
            if step >= args.steps:
                break
            t0 = time.time()
            state, metrics = step_fn(
                state, {k: jnp.asarray(v) for k, v in batch.items()})
            straggle.observe(time.time() - t0)
            if step % 10 == 0:
                print(f"[train] step {step} loss {float(metrics['loss']):.4f}")
            if (step + 1) % args.ckpt_every == 0:
                saver.save(step + 1, state)
        saver.wait()
        loader.close()
    print(f"[train] finished at step {args.steps}; "
          f"stragglers={straggle.flagged}")


if __name__ == "__main__":
    main()
