"""Host spans at the program's layer boundaries.

``span(name, **attrs)`` marks a block.  While a profiler trace collects
host events it enters a ``jax.profiler.TraceAnnotation`` of that name, so
the span lies on the host plane of the trace, on the device's clock; that
is the one exporter.  After ``enable()`` the span is also kept in memory,
timed on ``time.perf_counter()`` (the clock of ``Engine.events`` and
``Sequence.arrival``) with the name of the span open around it on the same
thread, until ``drain()``.  ``record(name, t0, t1, **attrs)`` keeps an
interval that does not nest lexically, such as a request's time in the
queue; it is kept after ``enable()`` only.

Off (the default, and no trace running), ``span`` returns one shared no-op
context and ``record`` returns at once.  A profiled run that never calls
``enable()`` keeps nothing in memory: its spans are in the trace alone.

``Engine.events`` stays the engine's counter log; this is the one span
system.
"""
from __future__ import annotations

import threading
import time
from typing import NamedTuple, Optional

from jax.profiler import TraceAnnotation


class Span(NamedTuple):
    name: str
    t0: float
    t1: float
    parent: Optional[str]          # the enclosing span on the same thread
    attrs: dict


_enabled = False
_spans: list = []
_local = threading.local()
_profiling = TraceAnnotation.is_enabled


def enable() -> None:
    global _enabled
    _enabled = True


def disable() -> None:
    global _enabled
    _enabled = False


def drain() -> list:
    """The spans kept so far, in the order they closed; the store empties."""
    global _spans
    out, _spans = _spans, []
    return out


class _Noop:
    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


class _Span:
    __slots__ = ("name", "attrs", "parent", "t0", "_ann")

    def __init__(self, name, attrs):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        stack = _local.__dict__.setdefault("stack", [])
        self.parent = stack[-1] if stack else None
        stack.append(self.name)
        self._ann = TraceAnnotation(self.name)
        self._ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        _local.stack.pop()
        _spans.append(Span(self.name, self.t0, t1, self.parent, self.attrs))
        return False


def span(name: str, **attrs):
    if _enabled:
        return _Span(name, attrs)
    if _profiling():
        return TraceAnnotation(name)
    return _NOOP


def record(name: str, t0: float, t1: float, **attrs) -> None:
    if _enabled:
        _spans.append(Span(name, t0, t1, None, attrs))
