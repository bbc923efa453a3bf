"""Find a cell, its configuration, its traffic mix and its metric readers by
the names ``BENCHMARK.json`` gives them. Imports nothing of JAX."""
from __future__ import annotations

import importlib.util
import json
import pathlib

#: the checkout root (bench/ -> one level up)
ROOT = pathlib.Path(__file__).resolve().parents[1]
HERE = pathlib.Path(__file__).resolve().parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    """The ``workloads`` entry named ``workload``; KeyError names the known
    ones."""
    for cell in bench["workloads"]:
        if cell["name"] == workload:
            return cell
    known = ", ".join(c["name"] for c in bench["workloads"])
    raise KeyError(f"no workload {workload!r} in BENCHMARK.json ({known})")


def load_config(bench: dict, name: str, root: pathlib.Path = ROOT) -> dict:
    for cfg in bench["configs"]:
        if cfg["name"] == name:
            return json.loads((root / cfg["file"]).read_text())
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def load_traffic(name: str, here: pathlib.Path = HERE) -> dict:
    path = here / "traffic" / f"{name}.json"
    if not path.is_file():
        raise KeyError(f"no traffic mix {name!r} ({path} is missing)")
    return json.loads(path.read_text())


def metrics_for(bench: dict, section: str, workload: str) -> list:
    """Entries of ``section`` ("end_to_end" | "per_layer") that the cell
    ``workload`` reports: those without ``workloads`` and those listing it."""
    return [m for m in bench[section]
            if workload in m.get("workloads", [workload])]


def reader(name: str, here: pathlib.Path = HERE):
    """The ``read(run) -> float | None`` function of ``metrics/<name>.py``."""
    path = here / "metrics" / f"{name}.py"
    if not path.is_file():
        raise KeyError(f"no reader for per-layer metric {name!r} ({path})")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
