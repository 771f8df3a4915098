"""Spans and counters of the serving path, on the profiler's clock.

One process-wide tracer, off by default.  ``span(name, **meta)`` opens a
``jax.profiler.TraceAnnotation`` (so the span lands on the host plane of
a profiler trace, beside the device's operations) and keeps a record
``(id, parent, name, t0, t1, meta)`` timed with ``time.perf_counter``;
the parent is the innermost open span of the same thread.  Spans that
touch requests carry their ids as ``meta["rids"]``.

The tracer records while it is enabled (``enable()`` .. ``disable()``)
and, by itself, while a JAX profiler session collects: the first span
that finds a session clears the last session's records and turns the
tracer on, the first span after the session ends turns it off again, and
that session's records stay until ``clear()`` or the next session, so a
long-running server keeps at most one profile's worth.  Off, a span
costs one flag check and the profiler's own "is a session collecting"
query, and returns a shared no-op context.  The profiler-driven switch is
a stop-gap for profiling code that does not call ``enable()`` itself; it
goes once every such caller does, leaving the flag check alone.

On, two hooks add what happens outside the spans: every XLA backend
compile (``jax.monitoring``'s ``backend_compile_duration``, which also
fires on a persistent-cache load) becomes a ``pb.compile`` span with
its ``fun_name`` and a ``compiles.<fun_name>`` count, and every garbage
collection a ``pb.gc`` span.
"""
from __future__ import annotations

import gc
import itertools
import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_profiling = jax.profiler.TraceAnnotation.is_enabled


class Record(NamedTuple):
    id: int
    parent: Optional[int]
    name: str
    t0: float
    t1: float
    meta: Dict


_on = False
_by_profiler = False        # turned on by a profiler session, not enable()
_records: List[Record] = []
_counters: Dict[str, int] = {}
_ids = itertools.count(1)
_stack = threading.local()
_wall_offset = 0.0          # perf_counter - time.time, taken at enable()
_gc_t0: List[float] = []


class _Noop:
    __slots__ = ()

    def __bool__(self):
        return False

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def end(self, t1=None, **meta):
        pass


_NOOP = _Noop()


class _Span:
    """A recording span; ``end(t1, **meta)`` sets its close time (the
    caller's own clock read) and adds meta known only at the end."""
    __slots__ = ("name", "meta", "t0", "t1", "id", "parent", "_ann")

    def __init__(self, name, t0, meta):
        self.name, self.t0, self.meta, self.t1 = name, t0, meta, None

    def __bool__(self):
        return True

    def __enter__(self):
        st = _stack.__dict__.setdefault("open", [])
        self.id = next(_ids)
        self.parent = st[-1] if st else None
        st.append(self.id)
        self._ann = jax.profiler.TraceAnnotation(self.name, pb_id=self.id)
        self._ann.__enter__()
        if self.t0 is None:
            self.t0 = time.perf_counter()
        return self

    def end(self, t1=None, **meta):
        self.t1 = t1
        self.meta.update(meta)

    def __exit__(self, *exc):
        t1 = time.perf_counter() if self.t1 is None else self.t1
        self._ann.__exit__(*exc)
        _stack.open.pop()
        _records.append(Record(self.id, self.parent, self.name, self.t0, t1,
                               self.meta))
        return False


def span(name: str, t0: Optional[float] = None, **meta):
    """A span around the ``with`` block; ``t0`` is the caller's own clock
    read of its start (``time.perf_counter``), read here if None."""
    global _by_profiler
    if not _on:
        if not _profiling():
            return _NOOP
        clear()
        enable()
        _by_profiler = True
    elif _by_profiler and not _profiling():
        disable()
        return _NOOP
    return _Span(name, t0, meta)


def count(name: str, n: int = 1) -> None:
    if _on:
        _counters[name] = _counters.get(name, 0) + n


def _on_compile(event, start, end, **kw):
    if event == COMPILE_EVENT:
        fun = str(kw.get("fun_name", "?"))
        _records.append(Record(next(_ids), None, "pb.compile",
                               start + _wall_offset, end + _wall_offset,
                               {"fun_name": fun}))
        count("compiles." + fun)


def _on_gc(phase, info):
    if phase == "start":
        _gc_t0.append(time.perf_counter())
    elif _gc_t0:
        _records.append(Record(next(_ids), None, "pb.gc", _gc_t0.pop(),
                               time.perf_counter(),
                               {"generation": info.get("generation")}))


def enable() -> None:
    """Turn the tracer on and register the compile and gc hooks."""
    global _on, _by_profiler, _wall_offset
    if _on:
        _by_profiler = False
        return
    _wall_offset = time.perf_counter() - time.time()
    jax.monitoring.register_event_time_span_listener(_on_compile)
    gc.callbacks.append(_on_gc)
    _on, _by_profiler = True, False


def disable() -> None:
    """Turn the tracer off and remove both hooks; the records stay."""
    global _on, _by_profiler
    if not _on:
        return
    jax.monitoring.unregister_event_time_span_listener(_on_compile)
    gc.callbacks.remove(_on_gc)
    _gc_t0.clear()
    _on = _by_profiler = False


def spans() -> List[Record]:
    return list(_records)


def counters() -> Dict[str, int]:
    return dict(_counters)


def clear() -> None:
    _records.clear()
    _counters.clear()
