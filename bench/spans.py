"""Attribution of a traced product to the program's own host spans.

The program wraps its eager stages in ``TraceAnnotation`` spans with stable
names (``repro.core.spans``): ``hemm.*`` for the stages of one HE MM,
``hlt.*`` inside each compiled HLT and ``ckks.*`` on the engine's
operations. ``reduce`` puts every host launch (``LAUNCH``) and host->device
transfer (``TRANSFER``) down to the innermost span that holds its start,
pairs each launch with the program it ran on each device plane, and splits
each idle interval of a device over the innermost spans that cover it, by
overlap. It works on the event lists ``trace.read_xspace`` returns, so a
hand-made list checks it without a chip.

The metric readers (``stage``) take the run record ``bench/run.py`` hands
them. Where it carries no span table yet, the first reader reads the trace
that the traced window left under ``TRACE_DIR``, prints the table to
standard error, fails the run above ``MAX_UNPAIRED`` unpaired launches and
keeps the table in the record for the other readers. A trace without
program spans (a program that predates them) gives ``None``, and no span
metric is read.
"""
from __future__ import annotations

import bisect
import pathlib
import sys

from bench.trace import MODULES, OPS, WINDOW, _union, read_xspace

#: where ``bench/run.py``'s traced window writes the profiler's trace
TRACE_DIR = pathlib.Path(__file__).resolve().parent / ".cache" / "trace"
#: name prefixes of the program's spans
PREFIXES = ("hemm.", "hlt.", "ckks.")
#: the host event of one program launch, and of one host->device transfer
LAUNCH = "PJRT_LoadedExecutable_Execute"
TRANSFER = "tpu::System::TransferToDevice"
#: the stage spans each per-layer metric sums
STAGES = {"hlt": ("hemm.step1", "hemm.hoist", "hemm.step2"),
          "tail": ("hemm.tail",)}
NO_SPAN = "(no span)"
#: the largest share of launches left without a device program (or the
#: reverse) before the traced run fails
MAX_UNPAIRED = 0.01


def is_program_span(name: str) -> bool:
    return name.startswith(PREFIXES)


def window(events: dict) -> tuple:
    """(start ns, end ns) of the traced products: from the first
    ``bench.product`` span's start to the last one's end."""
    spans = [(s, s + d) for _, name, s, d in events["host"] if name == WINDOW]
    if not spans:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    return min(s for s, _ in spans), max(e for _, e in spans)


class Timeline:
    """The program's spans on the host, properly nested, with the innermost
    one at every instant. ``spans[i]`` is ``(start_ns, end_ns, name)``,
    sorted by start (outer first where two start together), and
    ``parent[i]`` the index of the span around it or ``None``."""

    def __init__(self, host):
        self.spans = sorted(((s, s + d, name) for _, name, s, d in host
                             if is_program_span(name)),
                            key=lambda sp: (sp[0], -sp[1]))
        self.parent, stack = [], []
        children = [[] for _ in self.spans]
        for i, (s, _, _) in enumerate(self.spans):
            while stack and self.spans[stack[-1]][1] <= s:
                stack.pop()
            self.parent.append(stack[-1] if stack else None)
            if stack:
                children[stack[-1]].append(i)
            stack.append(i)
        # the intervals in which each span is the innermost one
        segs = []
        for i, (s, e, _) in enumerate(self.spans):
            t = s
            for c in children[i]:
                cs, ce, _ = self.spans[c]
                if cs > t:
                    segs.append((t, cs, i))
                t = max(t, ce)
            if e > t:
                segs.append((t, e, i))
        segs.sort()
        self._segs = segs
        self._starts = [s for s, _, _ in segs]

    def innermost(self, t):
        """Index of the innermost span that holds instant ``t``, or None."""
        i = bisect.bisect_right(self._starts, t) - 1
        if i >= 0 and t < self._segs[i][1]:
            return self._segs[i][2]
        return None

    def overlaps(self, a, b):
        """(span index, overlap ns) of each innermost span that covers part
        of [a, b)."""
        i = max(0, bisect.bisect_right(self._starts, a) - 1)
        while i < len(self._segs) and self._segs[i][0] < b:
            s, e, k = self._segs[i]
            o = min(e, b) - max(s, a)
            if o > 0:
                yield k, o
            i += 1


def pair(launches: int, modules: list) -> tuple:
    """Pairs (launch index, module) of one device plane, by order: a chip
    runs its programs in the order they were enqueued. ``modules`` are
    ``(start_ns, dur_ns)`` sorted by start. Returns (pairs, number left
    without a partner on either side)."""
    n = min(launches, len(modules))
    return list(enumerate(modules[:n])), abs(launches - len(modules))


def reduce(events: dict):
    """Span table of the traced products, or None without program spans.
    Device seconds and idle seconds are averaged over the device planes and
    clipped to the window of ``bench.product`` spans, as ``trace.reduce``
    does theirs."""
    tl = Timeline(events["host"])
    if not tl.spans:
        return None
    t0, t1 = window(events)
    n_sp = len(tl.spans)
    launches = sorted(s for _, name, s, _ in events["host"] if name == LAUNCH)
    transfers = [s for _, name, s, _ in events["host"] if name == TRANSFER]
    owner = [tl.innermost(s) for s in launches]
    # per span: [launches, transfers, device s, idle s], self (innermost) only
    own = [[0, 0, 0.0, 0.0] for _ in range(n_sp + 1)]   # [-1]: no span
    for k in owner:
        own[n_sp if k is None else k][0] += 1
    for s in transfers:
        k = tl.innermost(s)
        own[n_sp if k is None else k][1] += 1

    planes = sorted({ev[0] for ev in events["device"]})
    n = max(1, len(planes))
    unpaired = 0
    for plane in planes:
        mine = [ev[1:] for ev in events["device"] if ev[0] == plane]
        modules = sorted((s, d) for line, _, s, d in mine if line == MODULES)
        pairs, left = pair(len(launches), modules)
        unpaired = max(unpaired, left)
        for li, (s, d) in pairs:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                k = owner[li]
                own[n_sp if k is None else k][2] += (b - a) * 1e-9 / n
        busy = _union((max(s, t0), min(s + d, t1)) for line, _, s, d in mine
                     if line == OPS and min(s + d, t1) > max(s, t0))
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b <= a:
                continue
            covered = 0
            for k, o in tl.overlaps(a, b):
                own[k][3] += o * 1e-9 / n
                covered += o
            own[n_sp][3] += (b - a - covered) * 1e-9 / n

    incl = [list(row) for row in own]
    for i in range(n_sp - 1, -1, -1):
        if tl.parent[i] is not None:
            p = incl[tl.parent[i]]
            for j in range(4):
                p[j] += incl[i][j]
    rows = {}
    child_s = [0] * n_sp
    for i, (s, e, _) in enumerate(tl.spans):
        if tl.parent[i] is not None:
            child_s[tl.parent[i]] += e - s
    for i, (s, e, name) in enumerate(tl.spans):
        r = rows.setdefault(name, dict(count=0, wall_s=0.0, self_s=0.0,
                                       launches=0, transfers=0, device_s=0.0,
                                       idle_s=0.0, self_idle_s=0.0))
        r["count"] += 1
        r["wall_s"] += (e - s) * 1e-9
        r["self_s"] += (e - s - child_s[i]) * 1e-9
        r["launches"] += incl[i][0]
        r["transfers"] += incl[i][1]
        r["device_s"] += incl[i][2]
        r["idle_s"] += incl[i][3]
        r["self_idle_s"] += own[i][3]
    launches_out, transfers_out, device_out, idle_out = own[n_sp]
    rows[NO_SPAN] = dict(count=0, wall_s=0.0, self_s=0.0,
                         launches=launches_out, transfers=transfers_out,
                         device_s=device_out, idle_s=idle_out,
                         self_idle_s=idle_out)
    stages = {}
    for stage, names in STAGES.items():
        got = [rows[nm] for nm in names if nm in rows]
        stages[stage] = None if not got else {
            "wall_s": sum(r["wall_s"] for r in got),
            "dispatches": sum(r["launches"] for r in got),
            "device_s": sum(r["device_s"] for r in got)}
    return {"rows": rows, "stages": stages, "launches": len(launches),
            "unpaired": unpaired}


def check(summary) -> None:
    """Raise where more than ``MAX_UNPAIRED`` of the launches found no
    device program: the span-attributed device times would be wrong."""
    if summary and summary["unpaired"] > MAX_UNPAIRED * summary["launches"]:
        raise RuntimeError(
            f"{summary['unpaired']} of {summary['launches']} launches are "
            "unpaired with a device program: the trace dropped events or "
            "a launch ran no program")


def table(summary) -> str:
    """One row per span name, for standard error."""
    head = ("span", "count", "wall_s", "self_s", "launches", "transfers",
            "device_s", "idle_s", "self_idle_s")
    out = ["{:<16}{:>7}{:>12}{:>12}{:>10}{:>10}{:>12}{:>12}{:>12}".format(*head)]
    for name, r in sorted(summary["rows"].items(),
                          key=lambda kv: -kv[1]["wall_s"]):
        out.append("{:<16}{:>7}{:>12.6f}{:>12.6f}{:>10}{:>10}{:>12.6f}"
                   "{:>12.6f}{:>12.6f}".format(
                       name, r["count"], r["wall_s"], r["self_s"],
                       r["launches"], r["transfers"], r["device_s"],
                       r["idle_s"], r["self_idle_s"]))
    out.append(f"launches {summary['launches']}, unpaired "
               f"{summary['unpaired']}")
    return "\n".join(out)


def from_file(t: dict, trace_dir):
    """Span table of the trace the traced window left in ``trace_dir``, the
    one ``bench/run.py`` reduced to the summary ``t``; None without program
    spans. Prints the table and fails on too many unpaired launches."""
    paths = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        return None
    events = read_xspace(paths[-1])
    t0, t1 = window(events)
    if abs((t1 - t0) * 1e-9 - t["window_s"]) > 1e-9:
        raise RuntimeError(f"the trace under {trace_dir} is not the traced "
                           "window's")
    summary = reduce(events)
    if summary:
        print(table(summary), file=sys.stderr, flush=True)
        check(summary)
    return summary


def stage(run: dict, name: str, key: str):
    """``key`` of stage ``name`` per traced product, from a run record, or
    None where the trace held no such span."""
    t = run.get("trace")
    if not t:
        return None
    if "spans" not in t:
        t["spans"] = from_file(t, TRACE_DIR)
    got = t["spans"] and t["spans"]["stages"].get(name)
    if not got:
        return None
    return got[key] / t["products"]
