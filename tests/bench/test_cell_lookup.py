"""Cells, configurations, traffic mixes and metric readers are found by the
names BENCHMARK.json gives them, and the file keeps the benchmark's form."""
import json
import re

import pytest

from bench import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def test_find_cell_by_name(bench):
    cell = cells.find_cell(bench, "set-a-type-iv.seq")
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        "set-a-type-iv", "seq", 1)
    assert cells.load_config(bench, "set-a-type-iv")["shape"] == [64, 64, 64]


def test_unknown_cell_names_the_known_ones(bench):
    with pytest.raises(KeyError, match="set-a-type-iv.seq"):
        cells.find_cell(bench, "set-a-type-ii.seq")


def test_unknown_traffic_and_reader_are_refused():
    with pytest.raises(KeyError):
        cells.load_traffic("no-such-mix")
    with pytest.raises(KeyError):
        cells.reader("no_such_metric")


def test_every_cell_resolves_to_files(bench):
    for cell in bench["workloads"]:
        config = cells.load_config(bench, cell["config"])
        assert config["name"] == cell["config"]
        assert config["limits"]["rms_err"] > 0
        traffic = cells.load_traffic(cell["traffic"])
        assert traffic["in_flight"] == 1
        for m in cells.metrics_for(bench, "per_layer", cell["name"]):
            assert callable(cells.reader(m["name"]))


def test_metrics_for_honours_workload_lists(bench):
    names = [m["name"] for m in
             cells.metrics_for(bench, "end_to_end", "set-a-type-iv.seq")]
    assert names == ["hemm_s", "hbm_peak_gb", "setup_s"]
    per = cells.metrics_for(bench, "per_layer", "not-a-cell")
    assert per == [m for m in bench["per_layer"] if "workloads" not in m]


def test_benchmark_file_form(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in bench[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= {c["name"] for c in bench["workloads"]}
    for c in bench["configs"]:
        assert (cells.ROOT / c["file"]).is_file()
        data = json.loads((cells.ROOT / c["file"]).read_text())
        assert data["reduced"] == c["reduced"]
    assert len(json.dumps(bench)) < 64 * 1024


def test_cli_refuses_a_cpu_and_prints_no_result():
    import os
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(cells.ROOT / "bench" / "run.py"), "--workload",
         "set-a-type-iv.seq", "--seed", str(2**33), "--seconds", "1",
         "--trace", "0"], cwd=cells.ROOT, env=env, capture_output=True,
        text=True, timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert p.stdout.strip() == ""


@pytest.mark.parametrize("config,shape_type", [("set-a-type-iv", "type-iv"),
                                               ("set-a-type-i", "type-i")])
def test_configs_are_the_papers_set_a_twin_at_table_iii(config, shape_type):
    from repro.configs.fame_sets import MM_BENCHMARKS
    from repro.core.params import SET_A, HEParams
    data = json.loads((cells.HERE / "configs" / f"{config}.json").read_text())
    assert HEParams(**data["params"]) == SET_A.runtime_variant()
    assert tuple(data["shape"]) == MM_BENCHMARKS["set-a"][shape_type]
