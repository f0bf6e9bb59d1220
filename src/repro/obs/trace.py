"""Tracing spans with a near-zero-cost disabled path (DESIGN.md §16).

One ``Tracer`` holds a bounded in-memory buffer of Chrome-trace
"complete" events (``ph: "X"``, microsecond timestamps).  The API is
built so that EVERY production call site stays hot-path-safe when
tracing is off:

* ``span(name, **tags)`` — context manager.  Disabled (tracer off and
  no profiler recording), it returns a shared no-op singleton whose
  ``__enter__``/``__exit__`` are empty methods: no allocation, no
  clock read, no tag dict materialized beyond the call itself.
* ``timed(name, out, key, **tags)`` — like ``span`` but ALWAYS times
  (one ``perf_counter`` pair) and writes the elapsed seconds into
  ``out[key]``.  This is the migration target for the hand-rolled
  ``timings["stage"] = time.perf_counter() - t0`` pattern in
  ``refresh_index``/``build_index``: the dict consumers keep their
  numbers, and the same measurement becomes a trace event when the
  tracer is on — one clock, two views.
* ``event(name, t0, t1, **tags)`` — post-hoc emission for intervals
  the caller already measured (per-request lifecycle events derived
  from ``Request.t_sched``/``t_done``).  Disabled, it's one attribute
  check.

A span is live while its tracer is enabled OR a JAX profiler session
is recording (``jax.profiler.start_trace`` .. ``stop_trace``).  While
the profiler records, every live ``span``/``timed`` also opens a
``jax.profiler.TraceAnnotation`` of the same name with its tags as
metadata, so it lands on the profiler's host plane, on the device
trace's clock; it still goes into the buffer as well.  ``event()``
measured its interval after the fact and cannot reach the profiler:
it stays buffer-only, and only while the tracer is enabled.

Spans nest per-thread: each buffered event carries an ``id``, the
``parent`` id of the span that enclosed it on the same thread, and
its ``depth``, so readers can rebuild the tree and tests can assert
nesting/ordering invariants; events carry the thread id so
chrome://tracing lays concurrent flusher/refresh/export activity out
on separate rows.

A module-level default tracer (``get_tracer()``) is what the library
call sites use; ``serve.py --trace-out`` enables it and drains the
buffer into a Chrome-trace JSON at exit.
"""
from __future__ import annotations

import itertools
import threading
import time

from jax.profiler import TraceAnnotation

#: True while a JAX profiler session records (one native call)
_profiling = TraceAnnotation.is_enabled


class _NullSpan:
    """Shared do-nothing context manager — the disabled fast path."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


def _annotate(name: str, tags: dict):
    """An entered ``TraceAnnotation`` mirroring a span into the
    recording profiler session, or None when none records."""
    if not _profiling():
        return None
    ann = TraceAnnotation(name, **tags)
    ann.__enter__()
    return ann


class _Span:
    """One live span: records wall-clock bounds on exit and appends a
    Chrome "X" event to its tracer's buffer (and mirrors itself into
    the profiler while one records)."""

    __slots__ = ("_tracer", "name", "tags", "_t0", "_depth", "_id",
                 "_parent", "_ann")

    def __init__(self, tracer: "Tracer", name: str, tags: dict):
        self._tracer = tracer
        self.name = name
        self.tags = tags

    def _open(self) -> None:
        self._depth, self._id, self._parent = self._tracer._push()
        self._ann = _annotate(self.name, self.tags)

    def _close(self, t1: float) -> None:
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
        self._tracer._pop()
        self._tracer._emit(self.name, self._t0, t1, self.tags,
                           self._depth, self._id, self._parent)

    def __enter__(self):
        self._open()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self._close(time.perf_counter())
        return False


class _Timed(_Span):
    """Always-on timer that doubles as a span: elapsed seconds land in
    ``out[key]`` unconditionally, and it traces like a span when the
    tracer is enabled or the profiler records.  ``.elapsed`` is
    readable after exit."""

    __slots__ = ("_out", "_key", "_live", "elapsed")

    def __init__(self, tracer, name, out, key, tags):
        super().__init__(tracer, name, tags)
        self._out = out
        self._key = key
        self.elapsed = 0.0

    def __enter__(self):
        self._live = self._tracer.enabled or _profiling()
        if self._live:
            self._open()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.elapsed = t1 - self._t0
        if self._out is not None:
            self._out[self._key] = self.elapsed
        if self._live:
            self._close(t1)
        return False


class Tracer:
    """Bounded buffer of Chrome-trace events + the span/timed/event
    API.  Disabled by default; ``enable()`` flips one attribute read
    by every call site.  The buffer keeps at most ``max_events``
    (oldest dropped, drop count reported) so a long-lived server can
    leave tracing on without unbounded growth."""

    def __init__(self, *, enabled: bool = False,
                 max_events: int = 200_000):
        self.enabled = enabled
        self.max_events = max_events
        self._lock = threading.Lock()
        self._events: list[dict] = []
        self.dropped = 0
        self._local = threading.local()
        self._ids = itertools.count(1)
        # one fixed origin so every event's ts is a positive offset
        self._origin = time.perf_counter()

    # -- nesting (per-thread stack of open span ids) -----------------
    def _stack(self) -> list:
        stack = getattr(self._local, "stack", None)
        if stack is None:
            stack = self._local.stack = []
        return stack

    def _push(self) -> tuple[int, int, int | None]:
        """Open a span on this thread -> (depth, id, parent id)."""
        stack = self._stack()
        parent = stack[-1] if stack else None
        sid = next(self._ids)
        stack.append(sid)
        return len(stack) - 1, sid, parent

    def _pop(self) -> None:
        self._stack().pop()

    @property
    def depth(self) -> int:
        """Current thread's open-span depth."""
        return len(self._stack())

    # -- emission -----------------------------------------------------
    def _emit(self, name, t0, t1, tags, depth, sid, parent) -> None:
        ev = {
            "name": name,
            "ph": "X",
            "ts": (t0 - self._origin) * 1e6,
            "dur": max(0.0, (t1 - t0) * 1e6),
            "pid": 1,
            "tid": threading.get_ident() % 100_000,
            "args": dict(tags) if tags else {},
        }
        ev["args"]["id"] = sid
        if parent is not None:
            ev["args"]["parent"] = parent
        if depth:
            ev["args"]["depth"] = depth
        with self._lock:
            self._events.append(ev)
            if len(self._events) > self.max_events:
                drop = len(self._events) - self.max_events
                del self._events[:drop]
                self.dropped += drop

    # -- public API ---------------------------------------------------
    def enable(self, on: bool = True) -> "Tracer":
        self.enabled = on
        return self

    def span(self, name: str, **tags):
        """Context manager; the no-op singleton when the tracer is
        disabled and no profiler records."""
        if not self.enabled and not _profiling():
            return _NULL_SPAN
        return _Span(self, name, tags)

    def timed(self, name: str, out: dict | None, key: str, **tags):
        """Context manager that always times into ``out[key]`` and
        additionally traces when enabled or the profiler records."""
        return _Timed(self, name, out, key, tags)

    def event(self, name: str, t0: float, t1: float, **tags) -> None:
        """Emit a completed interval measured by the caller (both
        bounds on the ``perf_counter`` clock).  Buffer-only: the
        profiler cannot take an interval after the fact."""
        if not self.enabled:
            return
        self._emit(name, t0, t1, tags, 0, next(self._ids), None)

    def events(self) -> list[dict]:
        """Copy of the buffered events (chronological emit order)."""
        with self._lock:
            return list(self._events)

    def drain(self) -> list[dict]:
        """Return and clear the buffer."""
        with self._lock:
            out = self._events
            self._events = []
            return out

    def clear(self) -> None:
        with self._lock:
            self._events = []
            self.dropped = 0


# Module-level default: library call sites trace through this; it
# stays disabled (no-op spans, skipped events) unless a front end —
# serve.py --trace-out, a test — enables it, or a profiler records.
_DEFAULT = Tracer()


def get_tracer() -> Tracer:
    return _DEFAULT


def recording() -> bool:
    """True while spans on the default tracer are live (it is enabled,
    or a profiler records): the gate for a tag that costs host work."""
    return _DEFAULT.enabled or _profiling()


def span(name: str, **tags):
    """Span on the default tracer (the common call-site spelling)."""
    if not _DEFAULT.enabled and not _profiling():
        return _NULL_SPAN
    return _Span(_DEFAULT, name, tags)


def timed(name: str, out: dict | None, key: str, **tags):
    """Timed span on the default tracer (always populates ``out``)."""
    return _Timed(_DEFAULT, name, out, key, tags)


def event(name: str, t0: float, t1: float, **tags) -> None:
    """Post-hoc event on the default tracer (buffer-only)."""
    _DEFAULT.event(name, t0, t1, **tags)
