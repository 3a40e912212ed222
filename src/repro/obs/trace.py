"""Hierarchical span tracer on the profiler's clock.

The paper argues about query cost with a model (Table 2, Eq. 7-10);
``WorkStats`` counts the model's units (distance computations).  This
module records the third leg: measured wall time of the host's steps,
as a tree of :class:`Span`s, beside the device operations of a
``jax.profiler`` trace.

Design constraints (DESIGN.md §12):

  * one program.  A traced call runs the same jitted programs as an
    untraced one; spans mark the host's steps around them (dispatch,
    the wait for a result, copies, host sorts).  The device stages
    inside a program carry ``jax.named_scope`` names instead, which
    reach the profiler's op names.
  * never blocks.  Closing a span does not wait for the device: a span
    ends where the program itself already waits (``np.asarray`` of a
    result), so tracing cannot move work between spans or add a sync.
  * two switches.  A span records inside :func:`trace` (``obs.tracing``)
    and while a ``jax.profiler`` session is active.  With neither on, a
    span site costs one flag check and ``TraceAnnotation.is_enabled()``
    and returns a shared no-op context manager.
  * on the profiler's clock.  A recorded span enters a
    ``jax.profiler.TraceAnnotation`` of its name (so it lands on the
    host plane of the xplane, next to the device ops) and keeps an
    in-memory :class:`Span` stamped from ``time.time_ns()``: ``t0`` /
    ``t1`` are seconds since :data:`ORIGIN_NS`, so
    ``to_unix_ns(t) - profile_start_time`` is a span's offset in a
    profile (the ``Task Environment`` plane's ``profile_start_time`` is
    on the same clock).
  * bounded and nestable.  Past ``max_spans`` spans are counted in
    ``dropped``; spans nest through a per-thread stack, and a child
    inherits its parent's id attrs (:data:`ID_ATTRS`: the served
    request's ``rid``, the facade call's ``call``), so every span of
    one request or call carries its id.

Usage::

    from repro.obs import trace

    with trace.trace() as tr:           # enables, collects, disables
        index.search(Q, k=10)
    trace.save(tr)  # or export.to_chrome_trace(tr.spans)
"""
from __future__ import annotations

import dataclasses
import itertools
import threading
import time
from contextlib import contextmanager
from typing import Any, Iterator

from jax.profiler import TraceAnnotation

from .metrics import get_registry

__all__ = ["Span", "Tracer", "Trace", "get_tracer", "enabled", "enable",
           "disable", "span", "add_span", "recording", "now", "to_unix_ns",
           "new_call_id", "record_compile", "trace", "ORIGIN_NS", "ID_ATTRS"]

#: the flag of ``obs.tracing()`` regions (module attribute load + truth
#: test; the profiler check follows only when it is off)
_ENABLED: bool = False

#: True while a ``jax.profiler`` session is collecting (~40-150 ns)
_profiling = TraceAnnotation.is_enabled

#: ``time.time_ns()`` when this module was imported: span stamps are
#: seconds since it (ns resolution in a float64 for ~100 days)
ORIGIN_NS: int = time.time_ns()

#: attrs a child span copies from its parent: one request's or one
#: call's spans share them
ID_ATTRS = ("rid", "call")

_CALL_IDS = itertools.count()


def now() -> float:
    """The tracer's clock: seconds since :data:`ORIGIN_NS`."""
    return (time.time_ns() - ORIGIN_NS) * 1e-9


def to_unix_ns(t: float) -> int:
    """A tracer timestamp on the ``time.time_ns()`` clock, which the
    profiler's ``profile_start_time`` is on."""
    return ORIGIN_NS + round(t * 1e9)


def new_call_id() -> int:
    """A fresh id for the spans of one facade call."""
    return next(_CALL_IDS)


@dataclasses.dataclass
class Span:
    """One timed region.  ``parent`` indexes the tracer's span list
    (-1 for roots); ``attrs`` carries whatever the site recorded
    (shapes, ids, work counters)."""

    name: str
    t0: float  # seconds on the tracer's clock (see ``now``)
    t1: float
    parent: int
    attrs: dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def duration_s(self) -> float:
        return max(self.t1 - self.t0, 0.0)

    @property
    def duration_us(self) -> float:
        return self.duration_s * 1e6


class _NullSpan:
    """Shared no-op context manager returned while nothing records."""

    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _scalars(attrs: dict) -> dict:
    """The attrs a ``TraceAnnotation`` can carry as event stats."""
    return {k: v for k, v in attrs.items()
            if isinstance(v, (str, int, float)) and not isinstance(v, bool)}


class _Open:
    """A recording span: its profiler annotation and its stack entry."""

    __slots__ = ("tracer", "name", "attrs", "span", "index", "note")

    def __init__(self, tracer: "Tracer", name: str, attrs: dict):
        self.tracer, self.name, self.attrs = tracer, name, attrs

    def __enter__(self):
        self.span, self.index = self.tracer._open(self.name, self.attrs)
        self.note = TraceAnnotation(self.name)
        self.note.__enter__()
        return self.span

    def __exit__(self, *exc):
        s = self.span
        if s is not None:
            s.t1 = now()
            stack = self.tracer._stack()
            if stack and stack[-1] == self.index:
                stack.pop()
            meta = _scalars(s.attrs)
            if meta:
                self.note.set_metadata(**meta)
        self.note.__exit__(*exc)
        return False


class Tracer:
    """Process-global span collector (one per process is the intended
    use; tests may instantiate their own).  Bounded: past ``max_spans``
    new spans are counted in ``dropped`` instead of stored, so a traced
    long-running server cannot grow without bound."""

    def __init__(self, max_spans: int = 200_000):
        self.max_spans = int(max_spans)
        self.spans: list[Span] = []
        self.dropped = 0
        self._local = threading.local()

    def _stack(self) -> list[int]:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _new(self, name: str, t0: float, t1: float, attrs: dict
             ) -> Span | None:
        if len(self.spans) >= self.max_spans:
            self.dropped += 1
            return None
        stack = self._stack()
        parent = stack[-1] if stack else -1
        if parent >= len(self.spans):  # the collector was drained
            stack.clear()
            parent = -1
        if parent >= 0:
            up = self.spans[parent].attrs
            for key in ID_ATTRS:
                if key in up and key not in attrs:
                    attrs[key] = up[key]
        s = Span(name, t0, t1, parent, attrs)
        self.spans.append(s)
        return s

    def _open(self, name: str, attrs: dict) -> tuple[Span | None, int]:
        s = self._new(name, now(), 0.0, attrs)
        if s is None:
            return None, -1
        index = len(self.spans) - 1
        self._stack().append(index)
        return s, index

    # -- recording -------------------------------------------------------

    def span(self, name: str, **attrs) -> _Open:
        """Open a child span of whatever span is open on this thread.
        Its end is stamped at exit; nothing waits for the device."""
        return _Open(self, name, attrs)

    def add_span(self, name: str, t0: float, t1: float, **attrs) -> Span | None:
        """Record a span with explicit endpoints on the tracer's clock
        (e.g. a request's queue wait, whose start predates the current
        span).  Parented to the currently open span; it has no profiler
        annotation (one cannot be placed after the fact)."""
        return self._new(name, float(t0), float(t1), attrs)

    # -- draining --------------------------------------------------------

    def drain(self) -> list[Span]:
        """Return collected spans and reset the collector."""
        out, self.spans = self.spans, []
        self._stack().clear()
        self.dropped = 0
        return out

    def roots(self) -> list[Span]:
        return [s for s in self.spans if s.parent == -1]


_TRACER = Tracer()


def get_tracer() -> Tracer:
    return _TRACER


def enabled() -> bool:
    return _ENABLED


def recording() -> bool:
    """True where a span opened now would be recorded: inside
    ``obs.tracing()`` or a ``jax.profiler`` session."""
    return _ENABLED or _profiling()


def enable() -> None:
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    global _ENABLED
    _ENABLED = False


def span(name: str, **attrs):
    """Module-level span helper: a recording span inside
    ``obs.tracing()`` or a profiler session, the shared no-op context
    manager otherwise."""
    if not _ENABLED and not _profiling():
        return _NULL_SPAN
    return _Open(_TRACER, name, attrs)


def add_span(name: str, t0: float, t1: float, **attrs) -> Span | None:
    if not _ENABLED and not _profiling():
        return None
    return _TRACER.add_span(name, t0, t1, **attrs)


def record_compile(event: str, duration_s: float, **_kw) -> None:
    """``jax.monitoring`` listener: each backend compile adds one to
    ``jit_compiles_total`` and, while recording, a ``jit.compile`` span
    ending now, under whatever span the compile happened in."""
    if event != "/jax/core/compile/backend_compile_duration":
        return
    get_registry().counter(
        "jit_compiles_total", "XLA backend compiles in this process").inc()
    if _ENABLED or _profiling():
        t1 = now()
        _TRACER.add_span("jit.compile", t1 - float(duration_s), t1)


@dataclasses.dataclass
class Trace:
    """The result of one ``with trace.trace()`` region."""

    spans: list[Span] = dataclasses.field(default_factory=list)
    dropped: int = 0

    def roots(self) -> list[Span]:
        return [s for s in self.spans if s.parent == -1]


@contextmanager
def trace() -> Iterator[Trace]:
    """Enable tracing for the body, then hand the collected spans back
    on the yielded :class:`Trace`.  Re-entrant uses nest: only the
    outermost exit disables tracing and drains the collector."""
    tr = Trace()
    was_enabled = _ENABLED
    mark = len(_TRACER.spans)
    enable()
    try:
        yield tr
    finally:
        if not was_enabled:
            disable()
            tr.spans = _TRACER.drain()
            tr.dropped = 0
        else:  # nested: take only the spans this region added, with
            # parent indices rebased onto the slice
            sliced = _TRACER.spans[mark:]
            tr.spans = [
                dataclasses.replace(
                    s, parent=(s.parent - mark if s.parent >= mark else -1))
                for s in sliced
            ]
            tr.dropped = _TRACER.dropped
