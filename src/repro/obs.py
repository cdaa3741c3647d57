"""Named layers of the solver, for the profiler and for an in-memory record.

Two kinds of name, one module:

* ``scope(layer)`` names device work.  It is ``jax.named_scope``: the name
  enters the ``op_name`` metadata of every operation traced under it, so it
  costs nothing at run time.  An operation's layer is the innermost name of
  ``LAYERS`` in its ``op_name`` path.
* ``span(name, **attrs)`` names host work.  It always enters a
  ``jax.profiler.TraceAnnotation``, so a running profiler shows it on its
  host plane, on the device ops' clock.  While a recorder is open
  (``record()``), the span is also kept in memory: its parent, start and end
  (``time.perf_counter_ns``), its attrs and the fullest used device's
  ``peak_bytes_in_use`` at its end.

An open recorder also counts JAX's compile events (``COMPILE_EVENTS``): each
one with its seconds, the time it ended and the innermost span open then.
Nothing is kept while no recorder is open; there is no other switch.
"""

from __future__ import annotations

import contextlib
import time
from typing import NamedTuple, Optional

import jax

__all__ = ["LAYERS", "COMPILE_EVENTS", "TRACE", "COMPILE", "CACHE_LOAD",
           "scope", "scoped", "span", "record", "Span", "CompileEvent",
           "Recorder"]

LAYERS = (
    "axhelm",        # the element operator: Pallas kernel or reference
    "gs.q",          # Q: global to element-local copy
    "gs.qt",         # Q^T: element-local to global sum
    "gs.iface",      # interface gather and set of the sharded exchange
    "exchange",      # collectives: psum, ppermute
    "vec.dot",       # PCG inner products
    "vec.update",    # PCG x/r/p updates and the loop's scalar bookkeeping
    "vec.precond",   # the preconditioner (Jacobi)
    "vec.mask",      # the Dirichlet mask
)

TRACE = "/jax/core/compile/jaxpr_trace_duration"
COMPILE = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
# a backend compile's seconds include a cache load it made instead
COMPILE_EVENTS = (TRACE, COMPILE, CACHE_LOAD)


def scope(layer: str):
    """``jax.named_scope(layer)`` for one of ``LAYERS``, as a context
    manager (``scoped`` wraps a function)."""
    if layer not in LAYERS:
        raise ValueError(f"unknown layer {layer!r}; expected one of {LAYERS}")
    return jax.named_scope(layer)


def scoped(layer: str, fn):
    """``fn`` traced under ``scope(layer)``, a fresh scope each call."""

    def call(*args, **kwargs):
        with scope(layer):
            return fn(*args, **kwargs)

    return call


class Span(NamedTuple):
    name: str
    parent: Optional[int]       # index of the enclosing span, or None
    start_ns: int               # time.perf_counter_ns()
    end_ns: int
    attrs: dict
    peak_bytes: int             # fullest used device's peak at the end

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) / 1e9


class CompileEvent(NamedTuple):
    event: str                  # one of COMPILE_EVENTS
    at_ns: int                  # time.perf_counter_ns() when it was reported
    seconds: float
    span: Optional[int]         # innermost open span, or None


class Recorder:
    """Spans (in the order they opened) and compile events of one
    ``record()``."""

    def __init__(self, devices):
        self.devices = list(devices)
        self.spans: list = []
        self.compiles: list = []
        self._open: list = []

    def peak_bytes(self) -> int:
        return max([(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                    for d in self.devices] or [0])

    def children(self, i: int) -> list:
        return [j for j, s in enumerate(self.spans) if s.parent == i]

    def self_ns(self, i: int) -> int:
        """Span ``i``'s time less its children's."""
        return (self.spans[i].end_ns - self.spans[i].start_ns
                - sum(self.spans[j].end_ns - self.spans[j].start_ns
                      for j in self.children(i)))

    def compile_counts(self, start_ns=None, end_ns=None) -> dict:
        """{event: (count, seconds)} of the compile events reported in
        [start_ns, end_ns); a bound left None is open."""
        out = {e: (0, 0.0) for e in COMPILE_EVENTS}
        for c in self.compiles:
            if ((start_ns is None or c.at_ns >= start_ns)
                    and (end_ns is None or c.at_ns < end_ns)):
                n, s = out[c.event]
                out[c.event] = (n + 1, s + c.seconds)
        return out

    def _on_duration(self, event, seconds, **_):
        if event in COMPILE_EVENTS and _active is self:
            self.compiles.append(CompileEvent(
                event, time.perf_counter_ns(), float(seconds),
                self._open[-1] if self._open else None))


_active: Optional[Recorder] = None


@contextlib.contextmanager
def span(name: str, **attrs):
    """A host span: a profiler annotation, and a record while a recorder is
    open.  Usable as a context manager or a decorator."""
    rec = _active
    with jax.profiler.TraceAnnotation(name):
        if rec is None:
            yield
            return
        i = len(rec.spans)
        rec.spans.append(Span(name, rec._open[-1] if rec._open else None,
                              time.perf_counter_ns(), 0, attrs, 0))
        rec._open.append(i)
        try:
            yield
        finally:
            rec._open.pop()
            rec.spans[i] = rec.spans[i]._replace(
                end_ns=time.perf_counter_ns(), peak_bytes=rec.peak_bytes())


@contextlib.contextmanager
def record(devices=None):
    """Open a recorder (``devices`` default to the local devices) and yield
    it; spans and compile events go to the innermost open recorder."""
    global _active
    rec = Recorder(jax.local_devices() if devices is None else devices)
    outer, _active = _active, rec
    jax.monitoring.register_event_duration_secs_listener(rec._on_duration)
    try:
        yield rec
    finally:
        jax.monitoring.unregister_event_duration_listener(rec._on_duration)
        _active = outer
