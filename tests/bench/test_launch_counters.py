"""bench/launches.py and the two readers of the program's ``hlt.launch``
counters (``hlt_pad_share``, ``hlt_operand_gb``): against hand-made event
lists, against a real trace written here with the profiler, and against a
program whose launches carry no counters. Also the type II configuration
file against the paper's Table III."""
import json

import pytest

from bench import cells, launches
from bench.trace import WINDOW

MS = 1_000_000


def _events(launch_stats, window=(0, 100)):
    """One ``bench.product`` window (ms) and one ``hlt.launch`` per entry of
    ``launch_stats``: (start ms, counters)."""
    host = [("python3", WINDOW, window[0] * MS, (window[1] - window[0]) * MS)]
    host += [("python3", launches.LAUNCH_SPAN, int(t * MS), MS)
             for t, _ in launch_stats]
    return {"host": host,
            "launches": [(int(t * MS), dict(c)) for t, c in launch_stats]}


def _counters(slots, rotations, padding, key_bytes, diag_bytes):
    return dict(slots=slots, rotations=rotations, padding=padding,
                key_bytes=key_bytes, diag_bytes=diag_bytes)


def test_reduce_sums_the_launches_in_the_window():
    got = launches.reduce(_events([
        (5, _counters(264, 252, 10, 500, 60)),
        (40, _counters(512, 254, 258, 300, 40)),
        (120, _counters(9, 9, 9, 9, 9)),            # after the window
    ]))
    assert got == dict(_counters(776, 506, 268, 800, 100), launches=2)


def test_launches_without_counters_give_none():
    """A program that predates the counters: nothing to read, no error."""
    assert launches.reduce(_events([(5, {}), (40, {})])) is None
    run = {"trace": {"window_s": 0.1, "products": 1,
                     "launch_counters": None}}
    for name in ("hlt_pad_share", "hlt_operand_gb"):
        assert cells.reader(name)(run) is None
        assert cells.reader(name)({"phases": {}}) is None


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """A real trace: two products, each with two counted launches, and one
    counted launch outside the window."""
    import jax
    logdir = tmp_path_factory.mktemp("trace")
    jax.profiler.start_trace(str(logdir))
    try:
        with jax.profiler.TraceAnnotation(launches.LAUNCH_SPAN,
                                          **_counters(1, 1, 1, 1, 1)):
            pass
        for _ in range(2):
            with jax.profiler.TraceAnnotation(WINDOW):
                for c in (_counters(66, 62, 4, 120_000_000, 2_000_000),
                          _counters(6336, 3182, 3154, 4_000_000_000,
                                    1_250_000_000)):
                    with jax.profiler.TraceAnnotation(launches.LAUNCH_SPAN,
                                                      **c):
                        jax.numpy.ones(4).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(logdir.rglob("*.xplane.pb"))[-1]
    t0, t1 = launches.window(launches.read_events(path))
    return logdir, {"window_s": (t1 - t0) * 1e-9, "products": 2}


def test_readers_on_a_recorded_trace(recorded, monkeypatch):
    logdir, summary = recorded
    per = launches.from_file(summary, logdir)
    assert per == dict(_counters(6402, 3244, 3158, 4_120_000_000,
                                 1_252_000_000), launches=2)
    monkeypatch.setattr(launches, "TRACE_DIR", logdir)
    run = {"trace": dict(summary)}
    assert cells.reader("hlt_pad_share")(run) == pytest.approx(
        100 * 3158 / 6402)
    assert cells.reader("hlt_operand_gb")(run) == pytest.approx(5.372)
    assert run["trace"]["launch_counters"] == per      # read once per run


def test_a_trace_of_another_window_is_refused(recorded):
    logdir, summary = recorded
    with pytest.raises(RuntimeError, match="not the traced window"):
        launches.from_file(dict(summary, window_s=summary["window_s"] + 1),
                           logdir)


def test_type_ii_config_is_the_papers_set_a_twin_at_table_iii():
    from repro.configs.fame_sets import MM_BENCHMARKS
    from repro.core.params import SET_A, HEParams
    data = json.loads((cells.HERE / "configs" / "set-a-type-ii.json")
                      .read_text())
    assert HEParams(**data["params"]) == SET_A.runtime_variant()
    assert tuple(data["shape"]) == MM_BENCHMARKS["set-a"]["type-ii"]
    bench = cells.load_benchmark()
    assert cells.find_cell(bench, "set-a-type-ii.seq")["chips"] == 1
    assert cells.load_config(bench, "set-a-type-ii") == data
