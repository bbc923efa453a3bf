"""Reduction of a profiler trace to the benchmark's device metrics.

``read_xspace`` turns the profiler's ``.xplane.pb`` into plain event lists;
``reduce`` works on those lists alone, so a small recorded trace checks it
without a chip. Device planes are ``/device:TPU:<i>``: their ``XLA Ops``
line holds one event per operation and their ``XLA Modules`` line one event
per program launch, named after the jitted function. The host plane's
threads hold the benchmark's own span (``bench.product``) and what the host
was doing between launches.
"""
from __future__ import annotations

import bisect
import re

OPS, MODULES = "XLA Ops", "XLA Modules"
WINDOW = "bench.product"


def read_xspace(path) -> dict:
    """{"device": [(plane, line, name, start_ns, dur_ns)], "host": [(thread,
    name, start_ns, dur_ns)]} from one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    device, host = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name in (OPS, MODULES):
                    device.extend((plane.name, line.name, ev.name,
                                   ev.start_ns, ev.duration_ns)
                                  for ev in line.events)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                host.extend((line.name, ev.name, ev.start_ns, ev.duration_ns)
                            for ev in line.events)
    return {"device": device, "host": host}


def _union(intervals):
    """Merged, sorted (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(s, e, t0, t1):
    return max(s, t0), min(e, t1)


def reduce(events: dict, hlt_patterns, top: int = 10) -> dict:
    """Device metrics over the traced products.

    The window runs from the first ``bench.product`` span's start to the
    last one's end. ``busy_s`` is the union of the operations' intervals in
    it, averaged over the device planes; ``module_s`` sums each program's
    launches; ``hlt_s`` is the part of it whose name matches one of
    ``hlt_patterns`` and ``tail_s`` the rest. ``gaps`` are the longest idle
    intervals, each named by the innermost host span around its middle.
    """
    spans = [(s, s + d) for _, name, s, d in events["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    t0, t1 = min(s for s, _ in spans), max(e for _, e in spans)
    pats = [re.compile(p) for p in hlt_patterns]
    planes = sorted({ev[0] for ev in events["device"]})
    busy, ops, modules, merged = 0.0, {}, {}, []
    for plane in planes:
        mine = [ev[1:] for ev in events["device"] if ev[0] == plane]
        launches = sorted((s, s + d, name) for line, name, s, d in mine
                          if line == MODULES)
        starts = [s for s, _, _ in launches]
        iv = []
        for line, name, s, d in mine:
            if line == OPS:     # "%fusion.3 = (u32[...]) fusion(...)": the
                name = name.split(" = ", 1)[0]   # op, by the program it runs in
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < launches[i][1]:
                    name = f"{launches[i][2]}/{name}"
            s, e = _clip(s, s + d, t0, t1)
            if e <= s:
                continue
            table = ops if line == OPS else modules
            table[name] = table.get(name, 0.0) + (e - s) * 1e-9
            if line == OPS:
                iv.append((s, e))
        u = _union(iv)
        busy += sum(e - s for s, e in u) * 1e-9
        if not merged:
            merged = u
    n = max(1, len(planes))
    hlt = sum(v for k, v in modules.items() if any(p.search(k) for p in pats))
    gaps = []
    edges = [t0] + [x for iv in merged for x in iv] + [t1]
    for s, e in zip(edges[0::2], edges[1::2]):
        if e > s:
            gaps.append((s, e))
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:top]:
        mid = (s + e) / 2
        around = [(d, name) for _, name, hs, d in events["host"]
                  if hs <= mid <= hs + d and name != WINDOW]
        named.append([min(around)[1] if around else "host: no span",
                      (e - s) * 1e-9])
    return {
        "products": len(spans),
        "window_s": (t1 - t0) * 1e-9,
        "busy_s": busy / n,
        "module_s": modules,
        "hlt_s": hlt / n,
        "tail_s": (sum(modules.values()) - hlt) / n,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": named,
    }
