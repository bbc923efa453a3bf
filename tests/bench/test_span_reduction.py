"""bench/spans.py against hand-made event lists whose answers are known,
against the recorded v5e trace, and reading a real trace file back."""
import json
import pathlib

import pytest

from bench import cells, spans, trace

DATA = pathlib.Path(__file__).parent / "data"
DEV = "/device:TPU:0"
MS = 1_000_000


def _program():
    """One product in a 100 ms window: the four stages of ``hemm.call``,
    with the HLT spans inside Step 1 and one Mult (holding its key switch),
    Rescale and Add in the tail."""
    return [("python3", name, s * MS, (e - s) * MS) for name, s, e in [
        (trace.WINDOW, 0, 100),
        ("hemm.call", 1, 99),
        ("hemm.step1", 2, 10),
        ("hlt.hoist", 2, 4), ("hlt.launch", 4, 8), ("hlt.finish", 8, 10),
        ("hemm.hoist", 10, 12),
        ("hemm.step2", 12, 20),
        ("hemm.tail", 20, 98),
        ("ckks.mult", 20, 60), ("ckks.key_switch", 25, 55),
        ("ckks.rescale", 60, 80),
        ("ckks.add", 80, 98),
    ]]


#: launch times (ms) and the innermost span each starts in
LAUNCHES = [(3, "hlt.hoist"), (5, "hlt.launch"), (11, "hemm.hoist"),
            (15, "hemm.step2"), (30, "ckks.key_switch"), (57, "ckks.mult"),
            (70, "ckks.rescale"), (85, "ckks.add"), (99.5, spans.NO_SPAN)]


def _events(durations_ms=None, drop_last_module=False):
    """The program spans, one launch (0.1 ms on the host) per entry of
    ``LAUNCHES``, and per launch a device program of ``durations_ms[i]``
    that starts 0.2 ms after it, each holding one op as long as itself."""
    durations_ms = durations_ms or [1.0] * len(LAUNCHES)
    host = _program()
    dev = []
    for i, ((t, _), d) in enumerate(zip(LAUNCHES, durations_ms)):
        host.append(("main", spans.LAUNCH, int(t * MS), int(0.1 * MS)))
        if drop_last_module and i == len(LAUNCHES) - 1:
            continue
        s = int((t + 0.2) * MS)
        dev.append((DEV, trace.MODULES, f"jit_p{i}(1)", s, int(d * MS)))
        dev.append((DEV, trace.OPS, "fusion", s, int(d * MS)))
    host.append(("tasks", spans.TRANSFER, 31 * MS, MS))
    host.append(("tasks", spans.TRANSFER, 71 * MS, MS))
    return {"device": dev, "host": host}


def test_a_launch_goes_to_the_innermost_span():
    r = spans.reduce(_events())
    rows = r["rows"]
    for _, name in LAUNCHES:        # each leaf holds exactly its own launch
        if name != "ckks.mult":
            assert rows[name]["launches"] == 1, name
    assert rows["hlt.finish"]["launches"] == 0
    assert rows["ckks.mult"]["launches"] == 2      # its own and its key switch
    assert rows["hemm.step1"]["launches"] == 2
    assert rows["hemm.tail"]["launches"] == 4
    assert rows["hemm.call"]["launches"] == 8
    assert rows["ckks.key_switch"]["transfers"] == 1
    assert rows["ckks.rescale"]["transfers"] == 1
    assert r["launches"] == 9 and r["unpaired"] == 0
    assert r["stages"]["hlt"]["dispatches"] == 4
    assert r["stages"]["tail"]["dispatches"] == 4


def test_span_wall_and_self_time():
    rows = spans.reduce(_events())["rows"]
    assert rows["hemm.step1"]["wall_s"] == pytest.approx(0.008)
    assert rows["hemm.step1"]["self_s"] == pytest.approx(0.0)
    assert rows["ckks.mult"]["self_s"] == pytest.approx(0.010)
    assert rows["hemm.call"]["wall_s"] == pytest.approx(0.098)
    assert rows["hemm.call"]["self_s"] == pytest.approx(0.002)
    assert rows["hemm.tail"]["count"] == 1


def test_pairing_by_order_gives_each_launch_its_program():
    d = [0.5, 1.0, 2.0, 3.0, 4.0, 5.0, 6.0, 7.0, 8.0]
    r = spans.reduce(_events(d))
    assert r["stages"]["hlt"]["device_s"] == pytest.approx(0.0065)
    assert r["stages"]["tail"]["device_s"] == pytest.approx(0.022)
    assert r["rows"]["ckks.mult"]["device_s"] == pytest.approx(0.009)
    # the last program runs 99.7-107.7 ms: clipped to the window's end
    assert r["rows"][spans.NO_SPAN]["device_s"] == pytest.approx(0.0003)


def test_pair_leaves_the_surplus_of_either_side_unpaired():
    mods = [(10, 1), (20, 1)]
    assert spans.pair(3, mods) == ([(0, (10, 1)), (1, (20, 1))], 1)
    assert spans.pair(1, mods) == ([(0, (10, 1))], 1)
    assert spans.pair(2, mods)[1] == 0


def test_more_than_one_percent_unpaired_fails_the_run():
    r = spans.reduce(_events(drop_last_module=True))
    assert r["unpaired"] == 1 and r["launches"] == 9
    with pytest.raises(RuntimeError, match="1 of 9 launches"):
        spans.check(r)
    spans.check(spans.reduce(_events()))          # all paired: no error
    spans.check(None)                             # no spans: nothing to check
    assert spans.MAX_UNPAIRED == 0.01


def test_idle_time_is_split_over_the_spans_by_overlap():
    """Device busy 0-16 ms and 24-99 ms: the 16-24 ms gap is half Step 2,
    half the Mult; 99-100 ms is after the call."""
    host = _program()
    dev = [(DEV, trace.OPS, "fusion", 0, 16 * MS),
           (DEV, trace.OPS, "fusion", 24 * MS, 75 * MS)]
    r = spans.reduce({"device": dev, "host": host})
    rows = r["rows"]
    assert rows["hemm.step2"]["self_idle_s"] == pytest.approx(0.004)
    assert rows["ckks.mult"]["self_idle_s"] == pytest.approx(0.004)
    assert rows["ckks.mult"]["idle_s"] == pytest.approx(0.004)
    assert rows["hemm.tail"]["idle_s"] == pytest.approx(0.004)
    assert rows["hemm.tail"]["self_idle_s"] == 0
    assert rows["hemm.call"]["idle_s"] == pytest.approx(0.008)
    assert rows[spans.NO_SPAN]["idle_s"] == pytest.approx(0.001)


def test_two_device_planes_are_averaged():
    ev = _events()
    second = [("/device:TPU:1",) + e[1:3] + (e[3], 2 * e[4])
              for e in ev["device"]]
    r = spans.reduce({"device": ev["device"] + second, "host": ev["host"]})
    assert r["rows"]["hlt.launch"]["device_s"] == pytest.approx(0.0015)


def test_a_trace_without_program_spans_gives_none():
    ev = _events()
    ev["host"] = [h for h in ev["host"] if not spans.is_program_span(h[1])]
    assert spans.reduce(ev) is None


@pytest.mark.parametrize("name,stage,key", [
    ("hlt_wall_s", "hlt", "wall_s"),
    ("hlt_dispatches", "hlt", "dispatches"),
    ("hlt_span_device_s", "hlt", "device_s"),
    ("tail_wall_s", "tail", "wall_s"),
    ("tail_dispatches", "tail", "dispatches"),
    ("tail_span_device_s", "tail", "device_s"),
])
def test_metric_readers(name, stage, key):
    read = cells.reader(name)
    summary = spans.reduce(_events())
    want = summary["stages"][stage][key]
    assert want > 0
    assert read({"trace": {"products": 1, "spans": summary}}) == want
    assert read({"trace": {"products": 2, "spans": summary}}) == want / 2
    assert read({"trace": {"products": 1, "spans": None}}) is None
    assert read({"phases": {}}) is None
    only_tail = dict(summary, stages={stage: None})
    assert read({"trace": {"products": 1, "spans": only_tail}}) is None


def test_stage_values_of_the_hand_made_product():
    s = spans.reduce(_events())["stages"]
    assert s["hlt"]["wall_s"] == pytest.approx(0.018)   # 8 + 2 + 8 ms
    assert s["tail"]["wall_s"] == pytest.approx(0.078)


def test_table_has_a_row_per_span_name():
    text = spans.table(spans.reduce(_events()))
    names = {line.split()[0] for line in text.splitlines()[1:-1]}
    assert names == {n for _, n, _, _ in _program()[1:]} | {"(no"}
    assert text.splitlines()[-1] == "launches 9, unpaired 0"


# -- trace.reduce: gap names ------------------------------------------------


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_v5e_4ms.json").read_text())


#: trace.reduce of the recorded trace before program spans existed
RECORDED_GAPS = [
    ["host: no span", 0.000671591], ["ReadSyncFlag", 0.000644572],
    ["host: no span", 0.000540771], ["DoEnqueueProgram", 0.000445258],
    ["DeferredTpuAllocator::Allocate", 0.000273499],
    ["DeferredTpuAllocator::Allocate", 0.00027066000000000004],
    ["TpuLoadedExecutable::ExecuteLaunch", 0.000263074],
    ["CompleteCallbacks", 0.000242715], ["tpu::System::Execute", 0.000230379],
    ["tpu::System::Execute", 0.000180091]]
RECORDED = {"busy_s": 0.00022932300000000003, "window_s": 0.004,
            "tail_s": 0.00023927100000000002, "hlt_s": 0.0, "products": 1}


def test_recorded_trace_reads_as_before(recorded):
    r = trace.reduce(recorded, ["indexed", "hoist_db"])
    assert r["idle_gaps"] == RECORDED_GAPS
    assert {k: r[k] for k in RECORDED} == RECORDED
    assert spans.reduce(recorded) is None


def test_recorded_trace_under_a_tail_span(recorded):
    """A ``hemm.tail`` span over the recorded 4 ms: ``trace.reduce`` reads
    every number as before, and its 12 launches are the tail's (10 programs
    in the clipped window, so 2 are unpaired)."""
    (t0, length), = [(s, d) for _, n, s, d in recorded["host"]
                     if n == trace.WINDOW]
    spanned = dict(recorded, host=recorded["host"]
                   + [("python3", "hemm.tail", t0, length)])
    before = trace.reduce(recorded, ["indexed", "hoist_db"])
    after = trace.reduce(spanned, ["indexed", "hoist_db"])
    assert {k: v for k, v in after.items() if k != "idle_gaps"} == \
        {k: v for k, v in before.items() if k != "idle_gaps"}
    assert [g[1] for g in after["idle_gaps"]] == \
        [g[1] for g in before["idle_gaps"]]
    s = spans.reduce(spanned)
    assert s["stages"]["tail"]["dispatches"] == 12
    assert s["unpaired"] == 2
    assert s["stages"]["hlt"] is None
    assert s["stages"]["tail"]["device_s"] == pytest.approx(
        sum(v for k, v in before["module_s"].items()))
    with pytest.raises(RuntimeError):
        spans.check(s)


# -- reading the traced window's file ----------------------------------------


@pytest.fixture(scope="module")
def trace_files(tmp_path_factory):
    """Two real profiler traces of a small jitted product: one with a
    ``hemm.step1`` and a ``hemm.tail`` span inside ``bench.product``, one
    with no program span (a program that predates them)."""
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: x * 3 + 1)
    x = jnp.arange(1024.0)
    f(x).block_until_ready()
    dirs = {}
    for kind in ("spanned", "plain"):
        d = tmp_path_factory.mktemp(kind)
        with jax.profiler.trace(str(d)):
            with jax.profiler.TraceAnnotation(trace.WINDOW):
                if kind == "spanned":
                    with jax.profiler.TraceAnnotation("hemm.step1"):
                        f(x).block_until_ready()
                    with jax.profiler.TraceAnnotation("hemm.tail"):
                        for _ in range(3):
                            f(x).block_until_ready()
                else:
                    f(x).block_until_ready()
        path = sorted(d.rglob("*.xplane.pb"))[-1]
        t0, t1 = spans.window(trace.read_xspace(path))
        dirs[kind] = (d, {"products": 1, "window_s": (t1 - t0) * 1e-9})
    return dirs


def test_readers_read_the_traced_windows_file(trace_files, monkeypatch,
                                              capsys):
    d, t = trace_files["spanned"]
    monkeypatch.setattr(spans, "TRACE_DIR", d)
    run = {"trace": dict(t)}
    wall = cells.reader("tail_wall_s")(run)
    events = trace.read_xspace(sorted(d.rglob("*.xplane.pb"))[-1])
    want = spans.reduce(events)
    assert wall == want["stages"]["tail"]["wall_s"] > 0
    assert run["trace"]["spans"] == want           # kept for the next reader
    assert cells.reader("hlt_wall_s")(run) == want["stages"]["hlt"]["wall_s"]
    err = capsys.readouterr().err
    assert "hemm.tail" in err and "hemm.step1" in err
    assert err.count("unpaired") == 1               # the table, printed once


def test_a_file_without_program_spans_reads_none(trace_files, monkeypatch,
                                                 capsys):
    d, t = trace_files["plain"]
    monkeypatch.setattr(spans, "TRACE_DIR", d)
    for name in ("hlt_wall_s", "hlt_dispatches", "hlt_span_device_s",
                 "tail_wall_s", "tail_dispatches", "tail_span_device_s"):
        assert cells.reader(name)({"trace": dict(t)}) is None
    assert capsys.readouterr().err == ""


def test_a_stale_or_missing_file(trace_files, tmp_path, monkeypatch):
    d, t = trace_files["spanned"]
    monkeypatch.setattr(spans, "TRACE_DIR", d)
    with pytest.raises(RuntimeError, match="not the traced window's"):
        spans.stage({"trace": dict(t, window_s=t["window_s"] + 1)},
                    "tail", "wall_s")
    monkeypatch.setattr(spans, "TRACE_DIR", tmp_path)
    assert spans.stage({"trace": dict(t)}, "tail", "wall_s") is None
