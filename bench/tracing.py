"""From a profiler trace to per-layer device times.

A traced run records the window with ``jax.profiler`` and the benchmark's
own host spans (``jax.profiler.TraceAnnotation``): ``window`` around the
whole window, ``solve_call`` around each solve until its result is ready,
``bookkeeping`` around the harness's work between solves.

Device operations are the events of the ``XLA Ops`` line of each device
plane.  An event's name is its HLO instruction; control-flow containers
(``while``, ``conditional``, ``call``) span their bodies and are not
operations.  Each operation falls in one class, decided from its HLO text:

    axhelm    the instruction's name holds "axhelm" (the Pallas kernel)
    exchange  a collective: all-reduce, all-gather, reduce-scatter,
              all-to-all or collective-permute (or their async halves)
    gs        it reads or writes an integer array: an index map of Q,
              Q^T or the interface exchange (gather, scatter-add, their
              index arithmetic)
    vec       everything else: PCG dots and updates, mask, Jacobi, copies

Time on a device is split by a sweep over its operations' start and end
points: each instant goes to the first class in ``CLASSES`` that has an
operation running then, or to idle when none has.  So the classes and idle
add up to the window exactly, time in which a collective overlaps other
work is that work's, and ``exchange`` is the collective time left exposed.
"""

from __future__ import annotations

import glob
import os
import re
from typing import NamedTuple

__all__ = ["CLASSES", "Event", "Span", "classify", "read_xplane",
           "DeviceTimes", "reduce_device", "Reduction", "reduce_trace"]

CLASSES = ("axhelm", "gs", "vec", "exchange")
SPANS = ("window", "solve_call", "bookkeeping")
CONTAINERS = frozenset({"while", "conditional", "call"})
COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

_OPCODE = re.compile(r"\s([a-z][a-z0-9-]*)\(")
_INT_ARRAY = re.compile(r"\b[su](?:8|16|32|64)\[(\d[\d,]*)\]")


class Event(NamedTuple):
    name: str        # the HLO instruction, "%name = shape opcode(...)"
    start: float     # ns, on the trace's common clock
    end: float


class Span(NamedTuple):
    name: str
    start: float
    end: float


def instruction(name: str) -> str:
    """The instruction's own name: "fusion.17" of "%fusion.17 = ..."."""
    head = name.split(" = ", 1)[0]
    return head.lstrip("%").strip()


def opcode(name: str) -> str:
    rest = name.split(" = ", 1)[-1]
    m = _OPCODE.search(" " + rest)
    return m.group(1) if m else ""


def classify(name: str):
    """The class of one device operation, or None for a container."""
    op = opcode(name)
    if op in CONTAINERS:
        return None
    if "axhelm" in instruction(name):
        return "axhelm"
    if any(op.startswith(c) for c in COLLECTIVES):
        return "exchange"
    for dims in _INT_ARRAY.findall(name):
        size = 1
        for d in dims.split(","):
            size *= int(d)
        if size > 1:
            return "gs"
    return "vec"


def read_xplane(path: str):
    """(device events by device id, host spans) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        m = re.match(r"/device:[A-Z]+:(\d+)$", plane.name.strip())
        if m:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        Event(e.name, e.start_ns, e.end_ns)
                        for e in line.events]
        elif plane.name.strip().startswith("/host:"):
            for line in plane.lines:
                spans.extend(Span(e.name, e.start_ns, e.end_ns)
                             for e in line.events if e.name in SPANS)
    return devices, spans


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


class DeviceTimes(NamedTuple):
    """One device's split of a window, in ns."""

    busy: float                   # union of operation intervals
    by_class: dict                # class -> ns, exclusive; sums to busy
    gaps: list                    # idle intervals (start, end)
    ops: dict                     # "class:instruction" -> summed duration
    counts: dict                  # class -> number of operations


def reduce_device(events, t0: float, t1: float) -> DeviceTimes:
    """Split the window [t0, t1] of one device by class (see module doc)."""
    points = []
    ops, counts = {}, {c: 0 for c in CLASSES}
    for e in events:
        cls = classify(e.name)
        if cls is None:
            continue
        s, t = max(e.start, t0), min(e.end, t1)
        if t <= s:
            continue
        rank = CLASSES.index(cls)
        points.append((s, 1, rank))
        points.append((t, -1, rank))
        key = f"{cls}:{instruction(e.name)}"
        ops[key] = ops.get(key, 0.0) + (t - s)
        counts[cls] += 1
    points.sort()
    active = [0] * len(CLASSES)
    by_class = {c: 0.0 for c in CLASSES}
    gaps = []
    prev = t0
    for t, step, rank in points:
        if t > prev:
            top = next((i for i, n in enumerate(active) if n > 0), None)
            if top is None:
                gaps.append((prev, t))
            else:
                by_class[CLASSES[top]] += t - prev
            prev = t
        active[rank] += step
    if t1 > prev:
        gaps.append((prev, t1))
    busy = sum(by_class.values())
    return DeviceTimes(busy, by_class, gaps, ops, counts)


class Reduction(NamedTuple):
    window_ns: float
    busiest: int                  # device id
    times: DeviceTimes            # of the busiest device
    busy_mean_ns: float           # busy time averaged over the devices
    gaps: list                    # [(host span name, ns)], longest first
    device_ops: list              # [(name, ns)], most time first


def _span_at(t: float, spans) -> str:
    inside = [s for s in spans if s.start <= t <= s.end and s.name != "window"]
    if not inside:
        return "window"
    return min(inside, key=lambda s: s.end - s.start).name


def reduce_trace(devices: dict, spans: list, used, top: int = 10
                 ) -> Reduction:
    """Reduce a traced window: the ``window`` span bounds it; the devices
    ``used`` count (a used device with no event is idle throughout), and
    the busiest of them is reported."""
    windows = [s for s in spans if s.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    t0, t1 = windows[0].start, windows[0].end
    per = {d: reduce_device(devices.get(d, []), t0, t1) for d in used}
    if not any(t.busy > 0 for t in per.values()):
        raise ValueError("no device operation ran in the traced window")
    busiest = max(per, key=lambda d: per[d].busy)
    times = per[busiest]
    gaps = sorted(((_span_at((s + e) / 2, spans), e - s)
                   for s, e in times.gaps), key=lambda g: -g[1])[:top]
    ops = sorted(times.ops.items(), key=lambda kv: -kv[1])[:top]
    busy_mean = sum(t.busy for t in per.values()) / len(per)
    return Reduction(t1 - t0, busiest, times, busy_mean, gaps, ops)
