"""bench/trace.py reduce() against small traces whose answers are known."""
import json
import pathlib

import pytest

from bench import trace

DATA = pathlib.Path(__file__).parent / "data"
DEV = "/device:TPU:0"
MS = 1_000_000


def _handmade():
    """A 100 ms window: an HLT launch (10-30 ms) holding two ops, a tail
    program (50-60 ms) holding one op, an op outside any window, and host
    spans naming what the host did in the gaps."""
    dev = [
        (DEV, trace.MODULES, "jit_indexed(7)", 10 * MS, 20 * MS),
        (DEV, trace.OPS, "fusion.1", 10 * MS, 5 * MS),
        (DEV, trace.OPS, "custom-call.2", 16 * MS, 14 * MS),
        (DEV, trace.MODULES, "jit_remainder(3)", 50 * MS, 10 * MS),
        (DEV, trace.OPS, "fusion.1", 50 * MS, 10 * MS),
        (DEV, trace.OPS, "fusion.9", 150 * MS, 10 * MS),     # after the window
    ]
    host = [
        ("python", trace.WINDOW, 0, 100 * MS),
        ("python", "PjitFunction(remainder)", 30 * MS, 20 * MS),
        ("python", "dispatch", 35 * MS, 5 * MS),
        ("python", "decrypt", 70 * MS, 30 * MS),
    ]
    return {"device": dev, "host": host}


def test_handmade_trace():
    r = trace.reduce(_handmade(), ["indexed", "hoist_db"])
    assert r["products"] == 1
    assert r["window_s"] == pytest.approx(0.1)
    # union of ops: 10-15, 16-30, 50-60 ms = 29 ms (the 150 ms op is outside)
    assert r["busy_s"] == pytest.approx(0.029)
    assert r["hlt_s"] == pytest.approx(0.020)
    assert r["tail_s"] == pytest.approx(0.010)
    ops = dict((k, v) for k, v in r["device_ops"])
    assert ops == pytest.approx({"jit_indexed(7)/fusion.1": 0.005,
                                 "jit_indexed(7)/custom-call.2": 0.014,
                                 "jit_remainder(3)/fusion.1": 0.010})
    gaps = r["idle_gaps"]
    assert [round(g[1], 6) for g in gaps] == [0.04, 0.02, 0.01, 0.001]
    # 60-100 ms: decrypt; 30-50 ms: the innermost span at 40 ms is the
    # 5 ms dispatch (35-40 ms); 0-10 ms: no host span but the window
    assert gaps[0][0] == "decrypt"
    assert gaps[1][0] == "dispatch"
    assert gaps[2][0] == "host: no span"


def test_a_trace_without_the_window_span_is_refused():
    with pytest.raises(ValueError, match="bench.product"):
        trace.reduce({"device": [], "host": []}, ["indexed"])


def test_no_device_ops_means_all_idle():
    events = {"device": [], "host": [("python", trace.WINDOW, 0, 5 * MS)]}
    r = trace.reduce(events, ["indexed"])
    assert r["busy_s"] == 0 and r["hlt_s"] == 0 and r["tail_s"] == 0
    assert r["idle_gaps"] == [["host: no span", pytest.approx(0.005)]]


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "trace_v5e_4ms.json").read_text())


def test_recorded_trace_against_a_plain_count(recorded):
    """4 ms of a real product: busy time against a microsecond bitmap of
    the same op intervals, programs summed by name, ops named by program."""
    import numpy as np
    r = trace.reduce(recorded, ["indexed", "hoist_db"])
    (t0, length), = [(s, d) for _, n, s, d in recorded["host"]
                     if n == trace.WINDOW]
    assert r["window_s"] == pytest.approx(length * 1e-9)
    bitmap = np.zeros(int(length // 1000) + 1, bool)
    total = {}
    for _, line, name, s, d in recorded["device"]:
        if line == trace.OPS:
            a = max(0, int((s - t0) // 1000))
            b = min(bitmap.size, int(np.ceil((s + d - t0) / 1000)))
            bitmap[a:b] = True
        else:
            e = min(s + d, t0 + length)
            total[name] = total.get(name, 0) + max(0, e - s) * 1e-9
    assert r["busy_s"] == pytest.approx(bitmap.sum() * 1e-6, abs=2e-5)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["module_s"] == pytest.approx(total)
    # no HLT launch falls in these 4 ms of the tail
    assert r["hlt_s"] == 0
    assert r["tail_s"] == pytest.approx(sum(total.values()))
    assert all("(" in name and "/%" in name for name, _ in r["device_ops"])
    assert [g[1] for g in r["idle_gaps"]] == sorted(
        (g[1] for g in r["idle_gaps"]), reverse=True)
    assert len(r["idle_gaps"]) == 10
