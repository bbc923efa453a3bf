#!/usr/bin/env python3
"""Readings of the lower-precision control, on the chip, over many seeds.

    python3 bench/control.py --workload set-a-type-iv.seq --products 3 \
        --seeds 11 12 13

The control is the reference put in the program's place, one precision
step down: float64 ``A @ B`` computed in bfloat16 on the device, for the
same pool pairs, in the same order, as a run that times ``--products``
products. It is compared exactly as a run's products are, and has to come
out not correct. One JSON line per seed; the benchmark's own runs never
run this.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:1] = [str(ROOT), str(ROOT / "src")]


def reading(config: dict, traffic: dict, seed: int, products: int) -> dict:
    """The control's comparison for one seed: ``{"checks", "failed"}``."""
    from bench import loadgen, reference
    pairs = loadgen.pool(seed, config["shape"], traffic)
    refs = [reference.reference(A, B) for A, B in pairs]
    first = int(traffic["warmup_products"])
    outs = []
    for i in range(first, first + products):
        j = loadgen.pair_of(i, traffic)
        outs.append((j, reference.control(*pairs[j])))
    return reference.compare(outs, refs, config["limits"])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--products", type=int, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import jax
    from bench import cells
    bench = cells.load_benchmark()
    entry = cells.find_cell(bench, args.workload)
    config = cells.load_config(bench, entry["config"])
    traffic = cells.load_traffic(entry["traffic"])
    dev = jax.devices()[0]
    for seed in args.seeds:
        r = reading(config, traffic, seed, args.products)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "device": dev.device_kind, **r}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
