"""Host spans of the training loop, recorded only while a profiler session
records.

``span(name, **attrs)`` is a context manager.  With no ``jax.profiler``
session recording it returns one shared no-op object and records nothing:
its whole cost is the profiler's own "is a session on" flag test.  While a
session records, it enters ``jax.profiler.TraceAnnotation(name, **attrs)``,
so the span and its attributes stand in the ``.xplane.pb`` on the
profiler's clock beside the device's ops (xprof and TensorBoard show
them), and it appends a :class:`Span` to a bounded in-memory store that
``records()`` reads and ``reset()`` clears.

The store's times are ``time.perf_counter_ns()``, one monotonic host
clock; the profiler's host times are that clock minus a constant (the
session's start), so a reader that knows one span on both clocks maps
every other.  A span records its end even when an exception leaves it.
"""
from __future__ import annotations

import collections
import contextlib
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

MAX_RECORDS = 1 << 16   # the newest spans are kept


class Span(NamedTuple):
    name: str
    start_ns: int
    end_ns: int
    attrs: dict[str, Any]


_STORE: collections.deque[Span] = collections.deque(maxlen=MAX_RECORDS)
_OFF = contextlib.nullcontext()


class _Recording:
    __slots__ = ("name", "attrs", "start_ns", "_annotation")

    def __init__(self, name: str, attrs: dict[str, Any]):
        self.name, self.attrs = name, attrs

    def __enter__(self):
        self._annotation = TraceAnnotation(self.name, **self.attrs)
        self._annotation.__enter__()
        self.start_ns = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        end_ns = time.perf_counter_ns()
        self._annotation.__exit__(*exc)
        _STORE.append(Span(self.name, self.start_ns, end_ns, self.attrs))
        return False


def span(name: str, **attrs):
    """A span around the ``with`` block: recorded while a profiler session
    records, a shared no-op otherwise."""
    if not TraceAnnotation.is_enabled():
        return _OFF
    return _Recording(name, attrs)


def records() -> list[Span]:
    """The recorded spans, in the order they ended."""
    return list(_STORE)


def reset() -> None:
    _STORE.clear()
