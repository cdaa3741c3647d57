"""Per-layer device times from the program's own layer scopes, and the
readings of its recorder (``repro.obs``).

The program names its device work with ``jax.named_scope`` (``repro.obs``):
every operation traced under a scope carries the name in its ``op_name``,
and an operation's layer is the innermost of ``LAYERS`` in that path.  A
TPU trace keeps the ``op_name`` in the ``tf_op`` stat of each operation's
event metadata (as ``<op_name>:``), which ``jax.profiler.ProfileData`` does
not show; ``op_names`` reads it from the ``.xplane.pb`` itself.

Classes, in the order a sweep gives each instant of a device's window to
the first one running (as ``tracing.reduce_device`` does):

    axhelm, gs.qt, gs.q, gs.iface, exchange, vec, unscoped

``vec`` holds ``vec.dot``, ``vec.update``, ``vec.precond`` and
``vec.mask``; ``unscoped`` what names no layer (copies and slices the
compiler adds).  The classes and idle add up to the window.  Idle gaps go to
the innermost host span around their middle, the program's spans
(``setup.*``, ``solve.*``) included.
"""

from __future__ import annotations

import re
from typing import NamedTuple

from bench import tracing

__all__ = ["LAYERS", "CLASSES", "layer_class", "op_names", "read_xplane",
           "ScopedEvent", "ScopedTimes", "reduce_device", "ScopedReduction",
           "reduce_trace", "per_iteration_ms", "setup_spans", "window_span",
           "compile_seconds", "window_compiles"]

# the program's layer scopes (repro.obs.LAYERS; a test holds them equal)
LAYERS = ("axhelm", "gs.q", "gs.qt", "gs.iface", "exchange", "vec.dot",
          "vec.update", "vec.precond", "vec.mask")
CLASSES = ("axhelm", "gs.qt", "gs.q", "gs.iface", "exchange", "vec",
           "unscoped")
PROGRAM_SPANS = ("setup.", "solve.")
# repro.obs's compile events: a backend compile's seconds hold any cache
# load it made instead
TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"


def layer_class(op_name) -> str:
    """The class of an operation from its ``op_name`` (None: no name)."""
    for part in reversed((op_name or "").split("/")):
        if part in LAYERS:
            return "vec" if part.startswith("vec.") else part
    return "unscoped"


# -- the op_name of each device operation, from the .xplane.pb -------------

def _varint(buf, i):
    n = shift = 0
    while True:
        b = buf[i]
        i += 1
        n |= (b & 0x7F) << shift
        if b < 0x80:
            return n, i
        shift += 7


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints and
    fixed widths, bytes for length-delimited fields."""
    i, end = 0, len(buf)
    while i < end:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 1:
            value, i = int.from_bytes(buf[i:i + 8], "little"), i + 8
        elif wire == 5:
            value, i = int.from_bytes(buf[i:i + 4], "little"), i + 4
        elif wire == 2:
            n, i = _varint(buf, i)
            value, i = buf[i:i + n], i + n
        else:
            raise ValueError(f"protobuf wire type {wire} is not read here")
        yield field, value


def _map_entries(entries):
    for entry in entries:
        kv = dict(_fields(entry))
        yield kv.get(1, 0), kv.get(2, b"")


def op_names(path: str) -> dict:
    """{device plane name: {event name: op_name}} from the ``tf_op`` stat of
    each operation's event metadata (XSpace: planes 1; XPlane: name 2,
    event_metadata 4, stat_metadata 5; XEventMetadata: name 2, stats 5;
    XStat: metadata_id 1, str_value 5, ref_value 7)."""
    with open(path, "rb") as f:
        space = memoryview(f.read())
    out = {}
    for field, plane in _fields(space):
        if field != 1:
            continue
        name, events, stats = "", [], []
        for f, v in _fields(plane):
            if f == 2:
                name = bytes(v).decode()
            elif f == 4:
                events.append(v)
            elif f == 5:
                stats.append(v)
        if not re.match(r"/device:[A-Z]+:\d+$", name.strip()):
            continue
        stat_names = {k: dict(_fields(v)).get(2, b"")
                      for k, v in _map_entries(stats)}
        stat_names = {k: bytes(v).decode() for k, v in stat_names.items()}
        tf_op = [k for k, v in stat_names.items() if v == "tf_op"]
        named = {}
        for _, meta in _map_entries(events):
            event_name, value = None, None
            for f, v in _fields(meta):
                if f == 2:
                    event_name = bytes(v).decode()
                elif f == 5 and tf_op:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_op[0]:
                        value = (bytes(stat[5]).decode() if 5 in stat
                                 else stat_names.get(stat.get(7), ""))
            if event_name is not None and value:
                named[event_name] = value.rsplit(":", 1)[0]
        out[name.strip()] = named
    return out


class ScopedEvent(NamedTuple):
    name: str        # the HLO instruction, as tracing.Event
    start: float     # ns
    end: float
    op_name: object  # str, or None where the trace names none


def read_xplane(path: str):
    """(device events by device id, host spans) of one ``.xplane.pb``; the
    spans are the harness's (``tracing.SPANS``) and the program's."""
    from jax.profiler import ProfileData

    names = op_names(path)
    data = ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        pname = plane.name.strip()
        m = re.match(r"/device:[A-Z]+:(\d+)$", pname)
        if m:
            named = names.get(pname, {})
            for line in plane.lines:
                if line.name == "XLA Ops":
                    devices[int(m.group(1))] = [
                        ScopedEvent(e.name, e.start_ns, e.end_ns,
                                    named.get(e.name))
                        for e in line.events]
        elif pname.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    tracing.Span(e.name, e.start_ns, e.end_ns)
                    for e in line.events
                    if e.name in tracing.SPANS
                    or e.name.startswith(PROGRAM_SPANS))
    return devices, spans


class ScopedTimes(NamedTuple):
    """One device's split of a window by layer scope, in ns."""

    busy: float
    by_class: dict                # class -> ns, exclusive; sums to busy
    gaps: list                    # idle intervals (start, end)
    counts: dict                  # class -> number of operations


def reduce_device(events, t0: float, t1: float) -> ScopedTimes:
    """Split the window [t0, t1] of one device by layer scope."""
    points, counts = [], {c: 0 for c in CLASSES}
    for e in events:
        if tracing.opcode(e.name) in tracing.CONTAINERS:
            continue
        s, t = max(e.start, t0), min(e.end, t1)
        if t <= s:
            continue
        cls = layer_class(e.op_name)
        rank = CLASSES.index(cls)
        points.append((s, 1, rank))
        points.append((t, -1, rank))
        counts[cls] += 1
    points.sort()
    active = [0] * len(CLASSES)
    by_class = {c: 0.0 for c in CLASSES}
    gaps, prev = [], t0
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
    return ScopedTimes(sum(by_class.values()), by_class, gaps, counts)


class ScopedReduction(NamedTuple):
    window_ns: float
    busiest: int                  # device id
    times: ScopedTimes            # of the busiest device
    gaps: list                    # [(innermost span, ns)], longest first


def reduce_trace(devices: dict, spans: list, used, top: int = 10
                 ) -> ScopedReduction:
    """As ``tracing.reduce_trace``: the ``window`` span bounds the
    reduction and the busiest used device is reported."""
    windows = [s for s in spans if s.name == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    t0, t1 = windows[0].start, windows[0].end
    per = {d: reduce_device(devices.get(d, []), t0, t1) for d in used}
    if not any(t.busy > 0 for t in per.values()):
        raise ValueError("no device operation ran in the traced window")
    busiest = max(per, key=lambda d: per[d].busy)
    times = per[busiest]
    gaps = sorted(((tracing._span_at((s + e) / 2, spans), e - s)
                   for s, e in times.gaps), key=lambda g: -g[1])[:top]
    return ScopedReduction(t1 - t0, busiest, times, gaps)


def per_iteration_ms(m, cls: str):
    """A class's device time per PCG iteration, from what a metric reader
    gets: ``m.scoped`` (a ``ScopedReduction``) and ``m.total_iterations``;
    None where there is no scoped reduction."""
    scoped = getattr(m, "scoped", None)
    if scoped is None or m.total_iterations == 0:
        return None
    return scoped.times.by_class[cls] / m.total_iterations / 1e6


# -- the recorder's readings ------------------------------------------------

def window_span(rec):
    """The recorder's ``window`` span, or None."""
    found = [s for s in rec.spans if s.name == "window"]
    return found[0] if len(found) == 1 else None


def setup_spans(rec) -> list:
    """[name, seconds, peak bytes] of each ``setup.*`` span, in the order
    they opened."""
    return [[s.name, s.seconds, s.peak_bytes] for s in rec.spans
            if s.name.startswith("setup.")]


def compile_seconds(rec, before_ns) -> float:
    """Tracing and compiling (cache loads included) reported before
    ``before_ns``."""
    counts = rec.compile_counts(end_ns=before_ns)
    return counts[TRACE][1] + counts[COMPILE][1]


def window_compiles(rec, start_ns, end_ns) -> int:
    """Traces, compiles and cache loads reported in [start_ns, end_ns)."""
    counts = rec.compile_counts(start_ns, end_ns)
    return counts[TRACE][0] + counts[COMPILE][0] + counts[CACHE_LOAD][0]
