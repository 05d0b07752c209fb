"""Host-side span tracing with Chrome-trace/Perfetto JSON output.

A ``Span`` is a host-timed interval (``time.perf_counter_ns``) recorded as
a Chrome ``"ph": "X"`` complete event.  While a jax profiler is recording,
a span also opens a ``jax.profiler.TraceAnnotation`` of the same name on
the thread that opened it -- with or without a tracer (``annotation``) --
so a profiler trace carries the program's spans on the device trace's
clock.  That path never syncs; intervals recorded after the fact
(``Tracer.complete``) stay Chrome-only.  The tracer is process-wide and
thread-safe: each thread's spans land on its own track (``tid``).

Two invariants, enforced here rather than at every call site:

  * **zero perturbation** -- the tracer only ever *reads* clocks and
    (optionally) calls ``block_until_ready`` on values the caller was
    about to synchronise anyway.  Nothing recorded feeds back into traced
    computations, so training with tracing on is bitwise identical to
    tracing off (tests/test_obs.py asserts this).
  * **no-op under jit** -- a span opened while jax is *tracing* (inside
    ``jit``/``scan``) would record compile-time, not run-time, and a
    ``block_until_ready`` on a Tracer would fail.  ``_host_time_ok``
    checks ``jax.core.trace_ctx.is_top_level()`` (lazily -- this module never
    imports jax itself, keeping numpy-only importers like
    ``repro.data.stream`` jax-free) and the span degrades to ``NULL_SPAN``
    (and opens no profiler annotation).

This module is dependency-free (stdlib only) by design.
"""
from __future__ import annotations

import json
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional

def _host_time_ok() -> bool:
    """True when it is safe to record host wall time (i.e. we are NOT
    inside a jax trace).  jax is looked up lazily via ``sys.modules`` so
    importing this module never imports jax."""
    jax = sys.modules.get("jax")
    if jax is None:
        return True
    return jax.core.trace_ctx.is_top_level()


_annotation_cls: Any = None     # jax.profiler.TraceAnnotation, once seen


def _open_annotation(name: str) -> Any:
    """An entered ``jax.profiler.TraceAnnotation`` of ``name`` when a jax
    profiler is recording and no jax trace is in progress, else None.
    With no profiler this costs one flag test."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return None
        cls = _annotation_cls = jax.profiler.TraceAnnotation
    if not cls.is_enabled() or not _host_time_ok():
        return None
    ann = cls(name)
    ann.__enter__()
    return ann


def _block(value: Any) -> None:
    """``jax.block_until_ready`` on ``value`` if jax is importable and the
    value is a jax type; silently a no-op otherwise."""
    jax = sys.modules.get("jax")
    if jax is None or value is None:
        return
    try:
        jax.block_until_ready(value)
    except Exception:
        pass


class Span:
    """One open interval; close with ``__exit__`` or ``end()``.

    ``sync=value`` (or ``span.sync_on(value)``) makes the close a device
    boundary: ``block_until_ready(value)`` runs first, so the recorded
    duration covers the device work the caller is timing -- the explicit
    sync-boundary policy of DESIGN.md section 11.
    """

    __slots__ = ("tracer", "name", "cat", "args", "_t0", "_sync", "_ann")

    def __init__(self, tracer: "Tracer", name: str, cat: str,
                 args: Optional[Dict[str, Any]], sync: Any = None):
        self.tracer = tracer
        self.name = name
        self.cat = cat
        self.args = args
        self._sync = sync
        self._ann = _open_annotation(name)
        self._t0 = time.perf_counter_ns()

    def sync_on(self, value: Any) -> Any:
        """Register ``value`` to be synchronised at span close; returns it
        unchanged so call sites can wrap an expression."""
        self._sync = value
        return value

    def set(self, **kw) -> None:
        """Attach extra args to the span (merged at close)."""
        if self.args is None:
            self.args = {}
        self.args.update(kw)

    def end(self) -> float:
        """Close the span; returns duration in milliseconds.  The
        profiler annotation closes first: it never covers the sync."""
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        if self._sync is not None and self.tracer.sync_spans:
            _block(self._sync)
            self._sync = None
        t1 = time.perf_counter_ns()
        self.tracer._complete(self.name, self.cat, self._t0, t1,
                              self.args)
        return (t1 - self._t0) / 1e6

    def __enter__(self) -> "Span":
        return self

    def __exit__(self, *exc) -> None:
        self.end()


class _NullSpan:
    """The do-nothing span: returned when tracing is off or under jit.
    A single shared instance; every method is a cheap no-op."""

    __slots__ = ()

    def sync_on(self, value: Any) -> Any:
        return value

    def set(self, **kw) -> None:
        pass

    def end(self) -> float:
        return 0.0

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        pass


NULL_SPAN = _NullSpan()


class _Annotation(_NullSpan):
    """A span on the profiler alone: what ``annotation`` opens when no
    tracer records the span.  It never syncs."""

    __slots__ = ("_ann",)

    def __init__(self, ann: Any):
        self._ann = ann

    def end(self) -> float:
        ann, self._ann = self._ann, None
        if ann is not None:
            ann.__exit__(None, None, None)
        return 0.0

    def __exit__(self, *exc) -> None:
        self.end()


def annotation(name: str):
    """The span of ``name`` on the profiler alone: an open annotation
    while a jax profiler records at top level, else ``NULL_SPAN``."""
    ann = _open_annotation(name)
    return NULL_SPAN if ann is None else _Annotation(ann)


class Tracer:
    """Process-wide Chrome-trace event collector.

    Events accumulate in memory (a traced run is minutes, not days; the
    event dicts are small) and are written once by ``save``.  All methods
    are thread-safe; the hot path (``span`` with tracing off) never takes
    the lock.
    """

    def __init__(self, sync_spans: bool = True, pid: int = 0):
        self.sync_spans = sync_spans
        self.pid = pid if pid else os.getpid()
        self._events: List[dict] = []
        self._lock = threading.Lock()
        # thread ident (a large opaque int) -> small stable tid
        self._tids: Dict[int, int] = {}
        self._epoch_ns = time.perf_counter_ns()

    # -- track bookkeeping ------------------------------------------------
    def _tid(self) -> int:
        ident = threading.get_ident()
        with self._lock:
            tid = self._tids.get(ident)
            if tid is None:
                tid = len(self._tids)
                self._tids[ident] = tid
                name = threading.current_thread().name
                self._events.append({
                    "name": "thread_name", "ph": "M", "pid": self.pid,
                    "tid": tid, "args": {"name": name}})
        return tid

    def _us(self, t_ns: int) -> float:
        return (t_ns - self._epoch_ns) / 1e3

    # -- event emission ---------------------------------------------------
    def _complete(self, name: str, cat: str, t0_ns: int, t1_ns: int,
                  args: Optional[dict]) -> None:
        ev = {"name": name, "cat": cat, "ph": "X", "pid": self.pid,
              "tid": self._tid(),
              "ts": self._us(t0_ns), "dur": (t1_ns - t0_ns) / 1e3}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def span(self, name: str, cat: str = "host", sync: Any = None,
             **args) -> Span:
        """Open a span.  Under a jax trace this returns ``NULL_SPAN``."""
        if not _host_time_ok():
            return NULL_SPAN
        return Span(self, name, cat, args or None,
                    sync=sync if self.sync_spans else None)

    def complete(self, name: str, t0_ns: int, t1_ns: int, cat: str = "host",
                 **args) -> None:
        """Record an already-measured interval (endpoints captured
        elsewhere, e.g. around a sync); Chrome-only."""
        self._complete(name, cat, t0_ns, t1_ns, args or None)

    def instant(self, name: str, cat: str = "host", **args) -> None:
        if not _host_time_ok():
            return
        ev = {"name": name, "cat": cat, "ph": "i", "s": "t",
              "pid": self.pid, "tid": self._tid(),
              "ts": self._us(time.perf_counter_ns())}
        if args:
            ev["args"] = args
        with self._lock:
            self._events.append(ev)

    def counter(self, name: str, **values) -> None:
        """A Chrome counter event ("ph": "C") -- renders as a stacked
        area series in Perfetto."""
        if not _host_time_ok():
            return
        ev = {"name": name, "ph": "C", "pid": self.pid,
              "ts": self._us(time.perf_counter_ns()), "args": values}
        with self._lock:
            self._events.append(ev)

    # -- output -----------------------------------------------------------
    def events(self) -> List[dict]:
        with self._lock:
            return list(self._events)

    def save(self, path: str) -> str:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        with self._lock:
            doc = {"traceEvents": list(self._events),
                   "displayTimeUnit": "ms"}
        with open(path, "w") as f:
            json.dump(doc, f)
        return path
