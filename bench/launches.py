"""Counters the program attaches to its ``hlt.launch`` spans, per traced
product.

Each ``hlt.launch`` span carries as metadata what that launch of the HLT
kernel does and reads (``HLTPlan.launch_stats`` in the program): its
rotation ``slots``, the ``rotations`` among them that read a key, the
``padding`` slots, and the ``key_bytes`` and ``diag_bytes`` of key table
and diagonals it reads. ``reduce`` sums them over the launches that start
inside the traced window's ``bench.product`` spans; ``totals`` reads the
trace the traced window left under ``TRACE_DIR`` once per run and divides
by the products traced. A program whose launches carry no such counters
(one that predates them) gives None, and no metric is read.
"""
from __future__ import annotations

import pathlib

from bench.spans import TRACE_DIR, window

LAUNCH_SPAN = "hlt.launch"
COUNTERS = ("slots", "rotations", "padding", "key_bytes", "diag_bytes")


def read_events(path) -> dict:
    """{"host": [(thread, name, start_ns, dur_ns)], "launches": [(start_ns,
    {counter: value})]} of one ``.xplane.pb``: the host events, and the
    counters of each ``hlt.launch`` event."""
    from jax.profiler import ProfileData
    host, launches = [], []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for ev in line.events:
                host.append((line.name, ev.name, ev.start_ns,
                             ev.duration_ns))
                if ev.name == LAUNCH_SPAN:
                    launches.append((ev.start_ns, dict(ev.stats)))
    return {"host": host, "launches": launches}


def reduce(events: dict):
    """Each counter summed over the launches in the window, with the number
    of launches (``launches``); None where no launch carries a counter."""
    t0, t1 = window(events)
    sums = dict.fromkeys(COUNTERS, 0)
    n = 0
    for start, stats in events["launches"]:
        if not t0 <= start < t1 or not set(COUNTERS) <= set(stats):
            continue
        n += 1
        for k in COUNTERS:
            sums[k] += int(stats[k])
    return dict(sums, launches=n) if n else None


def from_file(t: dict, trace_dir):
    """Counters per product of the trace the traced window left in
    ``trace_dir``, the one ``bench/run.py`` reduced to the summary ``t``."""
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return None
    events = read_events(paths[-1])
    t0, t1 = window(events)
    if abs((t1 - t0) * 1e-9 - t["window_s"]) > 1e-9:
        raise RuntimeError(f"the trace under {trace_dir} is not the traced "
                           "window's")
    sums = reduce(events)
    if sums is None:
        return None
    return {k: v / t["products"] for k, v in sums.items()}


def totals(run: dict):
    """The launch counters per traced product of a run record, or None."""
    t = run.get("trace")
    if not t:
        return None
    if "launch_counters" not in t:
        t["launch_counters"] = from_file(t, TRACE_DIR)
    return t["launch_counters"]


