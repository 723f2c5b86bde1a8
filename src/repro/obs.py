"""Spans and counters of the served path, on the profiler's clock.

Three kinds of measurement, all kept in one process-wide table (like
``jax.monitoring``) that ``/statsz`` exports under ``stats.trace``:

- :class:`span` times a block of synchronous code. It opens a
  ``jax.profiler.TraceAnnotation`` of the same name, so while a profiler
  session is active the span sits on the trace's own clock beside the
  device's operations; JAX builds nothing when no session is active. It
  also adds the block's wall time and self time (its time less that of
  the spans nested in it on the same thread) to the table. A span must
  not hold an ``await``: coroutines on one event loop share a thread, and
  their spans would then interleave.
- :func:`record` adds a duration that starts on one thread and ends on
  another (a request's queue wait, its completion-to-resolution lag).
- :func:`count` adds to a monotone counter (bytes, rows).

The totals stay on: an operator reads them as deltas of :func:`snapshot`.
"""
from __future__ import annotations

import threading
import time
from typing import Dict

_lock = threading.Lock()
_times: Dict[str, list] = {}       # name -> [n, total_s, self_s]
_counters: Dict[str, int] = {}
_local = threading.local()
_Annotation = None


def _annotation(name: str, ids: dict):
    global _Annotation
    if _Annotation is None:
        from jax.profiler import TraceAnnotation
        _Annotation = TraceAnnotation
    return _Annotation(name, **ids)


def _add(name: str, total_s: float, self_s: float) -> None:
    with _lock:
        row = _times.get(name)
        if row is None:
            _times[name] = [1, total_s, self_s]
        else:
            row[0] += 1
            row[1] += total_s
            row[2] += self_s


class span:
    """``with span("bank.forest", wave=3):`` — time the block under
    ``name``; keyword ids go to the trace annotation. :meth:`set` adds ids
    known only inside the block (a request's uid once it is admitted)."""

    __slots__ = ("name", "_ids", "_ann", "_t0", "_child")

    def __init__(self, name: str, **ids):
        self.name = name
        self._ids = ids

    def __enter__(self) -> "span":
        stack = getattr(_local, "stack", None)
        if stack is None:
            stack = _local.stack = []
        stack.append(self)
        self._child = 0.0
        self._ann = _annotation(self.name, self._ids)
        self._ann.__enter__()
        self._t0 = time.perf_counter()
        return self

    def set(self, **ids) -> None:
        self._ann.set_metadata(**ids)

    def __exit__(self, *exc) -> None:
        dt = time.perf_counter() - self._t0
        self._ann.__exit__(*exc)
        stack = _local.stack
        stack.pop()
        if stack:
            stack[-1]._child += dt
        _add(self.name, dt, dt - self._child)


def record(name: str, seconds: float) -> None:
    """Add one duration measured across threads to ``name``'s row."""
    _add(name, seconds, seconds)


def count(name: str, n: int) -> None:
    """Add ``n`` to the monotone counter ``name``."""
    with _lock:
        _counters[name] = _counters.get(name, 0) + int(n)


def snapshot() -> dict:
    """The table as plain data: ``{"spans": {name: {"n", "total_s",
    "self_s"}}, "counters": {name: n}}``."""
    with _lock:
        return {"spans": {k: {"n": n, "total_s": t, "self_s": s}
                          for k, (n, t, s) in _times.items()},
                "counters": dict(_counters)}
