"""Read a profiler trace and reduce it to device time by kind of work.

``load`` takes the ``.xplane.pb`` that ``jax.profiler`` wrote and returns
plain tuples: for each TPU device the events of its ``XLA Ops`` line (what
the core runs, control ops such as ``while`` around their bodies) and of
its ``Async XLA Ops`` line (copies and collectives in flight), each named
by HLO instruction and program; and the host spans the harness recorded
(``train_step``, ``batch``, ``dispatch``, ``block``).  The interval
arithmetic below is what every per-layer metric is computed with.

A device is busy while an ``XLA Ops`` event runs.  Collective time is the
union of collective events on both lines; its exposed part is what no
other ``XLA Ops`` event covers.

Devices are combined by averaging: busy, collective and exposed time are
computed per device, then averaged over the devices the cell uses.
"""
from __future__ import annotations

import bisect
import dataclasses
import glob
import math
import os
import re
from collections import defaultdict

HOST_SPANS = ("train_step", "batch", "dispatch", "block")
OPS_LINE = "XLA Ops"               # the core's ops, control ops around their bodies
ASYNC_LINE = "Async XLA Ops"      # async ops in flight (DMAs, collectives)
MODULES_LINE = "XLA Modules"       # one event per program execution
_OP_NAME = re.compile(r"%?([^\s=]+)")


@dataclasses.dataclass
class Trace:
    devices: dict            # device id -> [(start_ns, end_ns, module, op, line)]
    host: list               # [(start_ns, end_ns, name)]


def find(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {trace_dir}, "
                                f"found {paths}")
    return paths[0]


def _module_of(modules, start):
    """The program (``XLA Modules`` event) running at ``start``: its name
    without the fingerprint, or "" outside any."""
    i = bisect.bisect_right(modules, (start, math.inf)) - 1
    if i >= 0 and modules[i][0] <= start < modules[i][1]:
        return modules[i][2]
    return ""


def load(path: str) -> Trace:
    """Device op events by TPU and the harness's host spans.  A device
    event is named by its HLO instruction (the event's name is the
    instruction's text, ``%name = ...``) and by the program whose execution
    it falls in; ``line`` is "ops" (the core) or "async" (in flight)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: list(line.events) for line in plane.lines}
            modules = sorted((ev.start_ns, ev.start_ns + ev.duration_ns,
                              ev.name.split("(", 1)[0])
                             for ev in lines.get(MODULES_LINE, []))
            events = []
            for line_name, tag in ((OPS_LINE, "ops"), (ASYNC_LINE, "async")):
                for ev in lines.get(line_name, []):
                    events.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                   _module_of(modules, ev.start_ns),
                                   _OP_NAME.match(ev.name).group(1), tag))
            devices[int(plane.name.rsplit(":", 1)[1])] = events
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name in HOST_SPANS:
                        host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                     ev.name))
    return Trace(devices=devices, host=sorted(host))


# ---------------------------------------------------------------------------
# interval arithmetic on [(start, end), ...]
# ---------------------------------------------------------------------------

def union(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted((s, e) for s, e, *_ in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals) -> float:
    return float(sum(e - s for s, e in union(intervals)))


def subtract(a, b) -> list[tuple[float, float]]:
    """The parts of union(a) that union(b) does not cover."""
    b = union(b)
    out, j = [], 0
    for s, e in union(a):
        while j < len(b) and b[j][1] <= s:
            j += 1
        k, cur = j, s
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def clip(intervals, lo: float, hi: float) -> list:
    return [(max(s, lo), min(e, hi), *rest) for s, e, *rest in intervals
            if e > lo and s < hi]


def leaves(events) -> list:
    """Drop events that contain another event of the same line (a control
    op around its body), so that time is attributed once."""
    evs = sorted(events, key=lambda x: (x[0], -x[1]))
    out = []
    for i, ev in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is not None and nxt[0] < ev[1] and nxt[1] <= ev[1]:
            continue
        out.append(ev)
    return out


# ---------------------------------------------------------------------------
# the reduction
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Reduction:
    window_ns: float
    steps: int
    busy_ns: list            # per device
    collective_ns: list      # per device: union of collective intervals
    exposed_ns: list         # per device: collective with no other op running
    ops: dict                # (module, op) -> {"count", "ns"}, summed over devices
    gaps: list               # [(seconds, host span name)] on the first device

    @property
    def n_devices(self) -> int:
        return len(self.busy_ns)


def reduce(trace: Trace, classify, devices=None) -> Reduction:
    """Reduce ``trace`` over its traced window, the span from the first
    ``train_step`` host span to the end of the last.

    ``classify(module, op)`` returns the op's ``hlo.Instr`` or None (an op
    of another program: busy time, never collective).
    """
    steps = [(s, e) for s, e, n in trace.host if n == "train_step"]
    if not steps:
        raise ValueError("the trace holds no train_step span")
    lo, hi = steps[0][0], steps[-1][1]
    ids = sorted(trace.devices) if devices is None else list(devices)
    busy, collective, exposed = [], [], []
    ops: dict = defaultdict(lambda: {"count": 0, "ns": 0.0})
    gaps = []
    for n, dev in enumerate(ids):
        evs = clip(trace.devices.get(dev, []), lo, hi)
        core = [ev for ev in evs if ev[4] == "ops"]
        coll, other = [], []
        for ev in leaves(core):
            instr = classify(ev[2], ev[3])
            (coll if instr is not None and instr.kind == "collective" else other).append(ev)
            rec = ops[(ev[2], ev[3])]
            rec["count"] += 1
            rec["ns"] += ev[1] - ev[0]
        for ev in evs:
            if ev[4] == "async":
                instr = classify(ev[2], ev[3])
                if instr is not None and instr.kind == "collective":
                    coll.append(ev)
        busy.append(length(core))
        collective.append(length(coll))
        exposed.append(length(subtract(coll, other)))
        if n == 0:
            gaps = _gaps(union(core), lo, hi, trace.host)
    return Reduction(window_ns=float(hi - lo), steps=len(steps), busy_ns=busy,
                     collective_ns=collective, exposed_ns=exposed, ops=dict(ops),
                     gaps=gaps)


def _gaps(busy, lo, hi, host) -> list[tuple[float, str]]:
    """Idle intervals of one device in [lo, hi], each named by the host
    span (other than ``train_step``) that overlaps it most, longest first."""
    idle = subtract([(lo, hi)], busy)
    spans = [(s, e, n) for s, e, n in host if n != "train_step"]
    out = []
    for s, e in idle:
        best, name = 0.0, "host: outside any span"
        for hs, he, hn in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, name = ov, f"host: {hn}"
        out.append(((e - s) * 1e-9, name))
    return sorted(out, reverse=True)
