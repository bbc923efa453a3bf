#!/usr/bin/env python3
"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload set-a-type-iv.seq --seed 1234 \
        --seconds 51 --trace 0

``--trace 0`` measures the cell's end-to-end metrics: set-up (from process
start to the window, the warm-up product included), then a closed loop of
encrypted products for ``--seconds`` (the window ends at the first product
boundary at or after it), then the chip's peak memory. ``--trace 1`` runs
the same set-up, traces one product with the profiler and reports the
per-layer metrics that ``bench/metrics/<name>.py`` read from the trace, the
set-up phases, the work count and the measured modular-multiplication peak.

Either way every timed product is decrypted and compared with the float64
reference once the window has closed. The last line of standard output is
one JSON object (``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``: each
number compared beside its limit); the last lines of standard error repeat
the checks. Without a TPU, or with fewer chips than the cell asks for, the
run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
# the checkout root and the program's sources, in place of bench/ itself
# (whose trace.py would shadow the standard library's trace module)
sys.path[:1] = [str(ROOT), str(ROOT / "src")]

TRACE_DIR = ROOT / "bench" / ".cache" / "trace"


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def find_devices(chips: int) -> dict:
    """The chips JAX finds, after the persistent compile cache is placed;
    exits non-zero without a TPU or with too few chips."""
    import repro  # noqa: F401  (the program's x64 setting, before any array)
    from repro.launch import compile_cache

    import jax
    log(f"compile cache: {compile_cache.enable()}")
    # cache every program, the tail's many small ones too
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX found only {devs[0].platform} devices")
    if len(devs) < chips:
        raise SystemExit(f"the cell needs {chips} chips, JAX found {len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def traced_window(cell) -> tuple:
    """One product under the profiler; returns (outputs, trace summary)."""
    import jax
    from bench import trace

    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0        # Python call events swamp the host
    opts.enable_hlo_proto = False
    i = cell.next
    jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            out = cell.product(i)
    finally:
        jax.profiler.stop_trace()
    paths = sorted(TRACE_DIR.rglob("*.xplane.pb"))
    if not paths:
        raise RuntimeError(f"the profiler wrote no trace under {TRACE_DIR}")
    patterns = json.loads((ROOT / "bench" / "programs.json").read_text())
    summary = trace.reduce(trace.read_xspace(paths[-1]),
                           patterns["hlt"]["patterns"])
    return [(i, out)], summary


def timed_window(cell, seconds: float) -> tuple:
    """Closed loop from the warm-up's end to the first product boundary at
    or after ``seconds``; returns (outputs, window seconds, the seconds at
    which each product ended)."""
    outs, ends, i = [], [], cell.next
    t0 = time.perf_counter()
    while True:
        outs.append((i, cell.product(i)))
        ends.append(time.perf_counter() - t0)
        i += 1
        if ends[-1] >= seconds:
            return outs, ends[-1], ends


def run_cell(bench: dict, cell_entry: dict, config: dict, traffic: dict,
             seed: int, seconds: float, trace: bool, device: dict,
             t_start: float = T_START) -> dict:
    """Everything after the look for a chip: set-up, window, peak memory,
    the comparison and the metrics. Returns the result object."""
    from bench import cells, loadgen, reference, work
    from bench.system import HemmCell, peak_bytes

    name = cell_entry["name"]
    cell = HemmCell(config, traffic, seed)
    log(f"{name} seed {seed}: plan {'cached' if cell.plan_cached else 'built'}"
        "; " + ", ".join(f"{k} {v!r}" for k, v in cell.phases.items()))
    log("peak bytes after " + ", ".join(f"{k} {v}"
                                         for k, v in cell.peaks.items()))
    t_window = time.perf_counter()
    setup_s = t_window - t_start
    if trace:
        outs, summary = traced_window(cell)
        window_s = summary["window_s"]
    else:
        outs, window_s, ends = timed_window(cell, seconds)
        log(f"products ended at {ends!r} s")
        summary = None
    device = dict(device, memory_peak_bytes=peak_bytes())
    log(f"window: {len(outs)} products in {window_s!r} s; peak "
        f"{device['memory_peak_bytes']} B")

    refs = [reference.reference(A, B) for A, B in cell.pairs]
    decrypted = [(loadgen.pair_of(i, traffic), cell.decrypt(out))
                 for i, out in outs]
    verdict = reference.compare(decrypted, refs, config["limits"])

    result = {"correct": verdict["failed"] == 0, "attempted": len(outs),
              "failed": verdict["failed"], "metrics": {}, "device": device}
    if trace:
        from bench import peak, peaks
        entry = peaks.lookup(device["kind"])
        measured = peak.measure()
        log(f"modmul peak: {measured['modmul_per_s']!r} /s (measured); "
            f"HBM {entry['hbm_bytes_per_s']!r} B/s ({entry['source']})")
        count = work.hemm(config["params"], *cell.step_sets())
        least, bound = work.least_time_s(count, measured["modmul_per_s"],
                                         entry["hbm_bytes_per_s"])
        log(f"HLT work per product: {count['modmults']} modmults, "
            f"{count['bytes']} B; least time {least!r} s, bound by {bound}")
        record = {"trace": summary, "phases": cell.phases, "work": count,
                  "modmul_per_s": measured["modmul_per_s"],
                  "hbm_bytes_per_s": entry["hbm_bytes_per_s"]}
        for m in cells.metrics_for(bench, "per_layer", name):
            value = cells.reader(m["name"])(record)
            if value is not None:
                result["metrics"][m["name"]] = {"value": value,
                                                "unit": m["unit"]}
        result["device"].update(busy_s=summary["busy_s"],
                                window_s=summary["window_s"])
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    else:
        e2e = {"hemm_s": window_s / len(outs),
               "hbm_peak_gb": device["memory_peak_bytes"] / 1e9,
               "setup_s": setup_s}
        for m in cells.metrics_for(bench, "end_to_end", name):
            result["metrics"][m["name"]] = {"value": e2e[m["name"]],
                                            "unit": m["unit"]}
    result["checks"] = verdict["checks"]
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import cells
    bench = cells.load_benchmark()
    entry = cells.find_cell(bench, args.workload)
    config = cells.load_config(bench, entry["config"])
    traffic = cells.load_traffic(entry["traffic"])
    device = find_devices(int(entry["chips"]))
    from bench import peaks
    peaks.lookup(device["kind"])
    log(f"device: {device['kind']} x{device['count']} ({device['platform']})")
    result = run_cell(bench, entry, config, traffic, args.seed, args.seconds,
                      bool(args.trace), device)
    for check, v in result["checks"].items():
        log(f"{check} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
