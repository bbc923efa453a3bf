"""Production serving launcher: sharded prefill/decode with continuous
batching.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-7b --smoke \\
        --requests 4
"""
import argparse

import numpy as np
import jax

import repro  # noqa: F401
from repro.configs import get_config, get_smoke_config
from repro.distributed.sharding import make_rules, set_rules
from repro.launch import compile_cache
from repro.launch.mesh import make_mesh_for
from repro.models import transformer as tf
from repro.serve.engine import ContinuousBatcher, ServeConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-7b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--tp", type=int, default=1)
    args = ap.parse_args()
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = make_mesh_for(args.tp, model_parallel=args.tp)
    rules = make_rules(mesh)
    set_rules(rules)
    with mesh:
        params = tf.init_params(cfg, jax.random.PRNGKey(0))
        batcher = ContinuousBatcher(
            cfg, ServeConfig(max_batch=4, max_len=128), params)
        rng = np.random.default_rng(0)
        for _ in range(args.requests):
            batcher.submit(
                rng.integers(0, cfg.vocab_size, size=8).astype(np.int32),
                max_new=args.max_new)
        steps = 0
        while batcher.step():
            steps += 1
    print(f"[serve] {args.requests} requests, {steps} decode steps")


if __name__ == "__main__":
    main()
