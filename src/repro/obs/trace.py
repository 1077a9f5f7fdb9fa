"""Structured tracing: spans, a thread-safe ring buffer, Perfetto export.

The serve hot path is instrumented with :func:`span` context managers at
the real seams — query encode, window planning, per-slab gather/wait/
search/merge, micro-batch dispatch — all HOST-side, strictly *around* the jit
boundaries. Spans never reach inside a traced function: the analyzer's
``trace_transparency`` contract machine-checks that installing a tracer
leaves every hot jaxpr byte-identical (and therefore adds no host-transfer
primitives) and changes zero result bytes.

Zero-overhead-when-disabled is the design center: with no tracer
installed, ``span(...)`` is one module-global read plus returning a
shared no-op singleton — no object allocation, no clock read, no lock.
Installing a :class:`Tracer` turns the same call sites into real spans
that record ``(name, t_start_ns, t_end_ns, attrs)`` into a bounded ring
buffer (old events are evicted, never the serve loop blocked).

Every live span carries three ids: its own ``span_id``, the ``parent_id``
of the innermost span open on the same thread when it began, or of the
span passed as ``parent`` (0 for a root), and the ``trace_id`` of its root
span. The same span also opens a
``jax.profiler.TraceAnnotation`` of its name with those ids as arguments,
so under a profiler session each span lands in the profiler's host trace,
on the device trace's clock, where a reader picks program spans out by
their ``span_id`` stat. With no profiler session the annotation records
nothing.

Export formats:

  * ``to_jsonl``  — one JSON object per line: ``{"name", "ts_us",
    "dur_us", "tid", "span_id", "parent_id", "trace_id", ...attrs}``
    (grep/jq-friendly);
  * ``to_chrome`` — Chrome ``trace_event`` JSON (``{"traceEvents":
    [...]}``, complete ``"ph": "X"`` events, the ids in ``args``) that
    https://ui.perfetto.dev and ``chrome://tracing`` open directly.

This module imports only the stdlib at module level on purpose: it is
imported from ``repro.core.pipeline``, ``repro.core.search``,
``repro.serve.engine`` and ``repro.serve.scheduler`` — both sides of the
core<->serve boundary — so importing anything from ``repro`` here would
create a cycle. The ``analyze --imports`` leaf-module check enforces this.
The profiler's annotation class is looked up when a :class:`Tracer` is
made, so the disabled path never touches JAX.
"""
from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import deque
from typing import Any, Mapping, NamedTuple


class TraceEvent(NamedTuple):
    """One completed span. Times are ``time.perf_counter_ns`` values —
    monotonic and comparable within a process, not wall-clock epochs."""

    name: str
    t_start_ns: int
    t_end_ns: int
    tid: int                      # recording thread ident
    attrs: Mapping[str, Any]      # small JSON-able payload (rows, bytes, ...)
    span_id: int = 0              # 0: recorded without ids
    parent_id: int = 0            # innermost open span on the thread, or 0
    trace_id: int = 0             # the root span's id

    @property
    def dur_ns(self) -> int:
        return self.t_end_ns - self.t_start_ns


ID_KEYS = ("span_id", "parent_id", "trace_id")


def _ids(ev: TraceEvent) -> dict:
    return {k: getattr(ev, k) for k in ID_KEYS}


class _OpenSpans(threading.local):
    """Per thread: the stack of live spans, innermost last."""

    def __init__(self):
        self.stack: list = []


class Tracer:
    """Thread-safe in-process span sink with a bounded ring buffer.

    ``capacity`` bounds memory: the buffer keeps the most recent events
    and counts evictions in :attr:`n_dropped` (a serve loop must never
    grow without bound or block on its own instrumentation).
    """

    def __init__(self, capacity: int = 1 << 16):
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        from jax.profiler import TraceAnnotation

        self.capacity = capacity
        self._buf: deque[TraceEvent] = deque(maxlen=capacity)
        self._lock = threading.Lock()
        self._recorded = 0
        self._annotation = TraceAnnotation
        self._ids = itertools.count(1)     # next() is atomic under the GIL
        self._open = _OpenSpans()

    # ------------------------------------------------------------------
    def record(self, name: str, t_start_ns: int, t_end_ns: int,
               attrs: Mapping[str, Any] | None = None, span_id: int = 0,
               parent_id: int = 0, trace_id: int = 0) -> None:
        ev = TraceEvent(name, int(t_start_ns), int(t_end_ns),
                        threading.get_ident(), attrs or {}, span_id,
                        parent_id, trace_id)
        with self._lock:
            self._buf.append(ev)
            self._recorded += 1

    def events(self) -> list[TraceEvent]:
        """Snapshot of the buffered events, oldest first."""
        with self._lock:
            return list(self._buf)

    def clear(self) -> None:
        with self._lock:
            self._buf.clear()
            self._recorded = 0

    @property
    def n_recorded(self) -> int:
        with self._lock:
            return self._recorded

    @property
    def n_dropped(self) -> int:
        with self._lock:
            return max(0, self._recorded - len(self._buf))

    # ------------------------------------------------------------------
    # Export
    # ------------------------------------------------------------------
    def to_jsonl(self, path: str) -> int:
        """One JSON object per line; returns the number of events written."""
        events = self.events()
        with open(path, "w") as f:
            for ev in events:
                f.write(json.dumps(event_dict(ev), sort_keys=True,
                                   separators=(",", ":")) + "\n")
        return len(events)

    def to_chrome(self, path: str) -> int:
        """Chrome/Perfetto ``trace_event`` JSON; returns the event count."""
        events = self.events()
        pid = os.getpid()
        out = {
            "displayTimeUnit": "ms",
            "traceEvents": [
                {"name": ev.name, "ph": "X", "pid": pid, "tid": ev.tid,
                 "ts": ev.t_start_ns / 1e3, "dur": ev.dur_ns / 1e3,
                 "args": {**ev.attrs, **_ids(ev)}}
                for ev in events
            ],
        }
        with open(path, "w") as f:
            json.dump(out, f, sort_keys=True, separators=(",", ":"))
            f.write("\n")
        return len(events)


def event_dict(ev: TraceEvent) -> dict:
    """The JSON-lines schema of one event (also what the report loader
    reconstructs from either export format)."""
    d = {"name": ev.name, "ts_us": ev.t_start_ns / 1e3,
         "dur_us": ev.dur_ns / 1e3, "tid": ev.tid}
    d.update(ev.attrs)
    d.update(_ids(ev))
    return d


# ---------------------------------------------------------------------------
# The span() fast path: module-global tracer, shared no-op singleton
# ---------------------------------------------------------------------------


class _Span:
    """A live span: ids, profiler annotation and clock read on enter,
    record on exit. ``add(**attrs)`` attaches facts learned mid-span (bytes
    fetched, rows survived)."""

    __slots__ = ("_tracer", "_name", "_attrs", "_parent", "_t0", "_ann",
                 "_stack", "span_id", "parent_id", "trace_id")

    def __init__(self, tracer: Tracer, name: str, attrs: dict, parent=None):
        self._tracer = tracer
        self._name = name
        self._attrs = attrs
        self._parent = parent

    def add(self, **attrs) -> None:
        self._attrs.update(attrs)

    def __enter__(self) -> "_Span":
        t = self._tracer
        self._stack = stack = t._open.stack
        self.span_id = next(t._ids)
        up = self._parent if isinstance(self._parent, _Span) else (
            stack[-1] if stack else None)
        if up is not None:
            self.parent_id, self.trace_id = up.span_id, up.trace_id
        else:
            self.parent_id, self.trace_id = 0, self.span_id
        stack.append(self)
        self._ann = t._annotation(self._name, span_id=self.span_id,
                                  parent_id=self.parent_id,
                                  trace_id=self.trace_id)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter_ns()
        self._ann.__exit__(*exc)
        self._stack.remove(self)
        self._tracer.record(self._name, self._t0, t1, self._attrs,
                            self.span_id, self.parent_id, self.trace_id)


class _NoopSpan:
    """The disabled fast path: a shared singleton whose enter/exit/add do
    nothing — ``with span(...)`` costs one global read when tracing is off."""

    __slots__ = ()

    def add(self, **attrs) -> None:
        pass

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NOOP_SPAN = _NoopSpan()

_tracer: Tracer | None = None


def span(name: str, *, parent=None, **attrs):
    """Context manager timing one named stage. With no tracer installed
    this returns the shared no-op singleton (the zero-overhead path).
    ``parent``, a live span of another thread, makes this span its child
    (work a thread does for a span open elsewhere); by default the parent
    is the innermost span open on this thread."""
    t = _tracer
    if t is None:
        return NOOP_SPAN
    return _Span(t, name, attrs, parent)


def install(tracer: Tracer) -> Tracer:
    """Make ``tracer`` the process-wide span sink; returns it."""
    global _tracer
    _tracer = tracer
    return tracer


def uninstall() -> None:
    global _tracer
    _tracer = None


def current() -> Tracer | None:
    return _tracer


def enabled() -> bool:
    return _tracer is not None
