"""Program spans on the profiler's clock.

``span(name, **attrs)`` marks a stretch of host work.  While no profiler
session runs it returns a shared no-op object, so an untraced program pays
one check per span.  While a session runs (``jax.profiler.trace`` or
``start_trace``) it opens a ``jax.profiler.TraceAnnotation``, which puts the
span into the trace's host plane on the device trace's clock, and on exit
appends a :class:`Span` to a bounded in-memory log that :func:`records`
returns.  The profiler is the only switch: there is no option, no
environment variable and no exporter.

Spans nest per thread; each record names its enclosing span's id.
Attributes are small ints or strings (a request id, a count of files),
settable while the span is open with ``set``.  Times come from
``time.monotonic()``.
"""

from __future__ import annotations

import itertools
import threading
import time
from typing import Any, NamedTuple

from jax.profiler import TraceAnnotation

#: spans kept before further ones are dropped (and counted)
MAX_SPANS = 1 << 18


class Span(NamedTuple):
    sid: int
    name: str
    t0: float
    t1: float
    #: ``sid`` of the enclosing span on the same thread, or None
    parent: int | None
    thread: int
    attrs: dict


class Log(NamedTuple):
    spans: list[Span]
    #: spans not kept because the log was full, and when the first was lost
    dropped: int
    first_drop: float | None


_ids = itertools.count()
_local = threading.local()
_lock = threading.Lock()
_spans: list[Span] = []
_dropped = 0
_first_drop: float | None = None


def _stack() -> list[int]:
    stack = getattr(_local, "stack", None)
    if stack is None:
        stack = _local.stack = []
    return stack


def _keep(rec: Span) -> None:
    global _dropped, _first_drop
    with _lock:
        if len(_spans) < MAX_SPANS:
            _spans.append(rec)
            return
        _dropped += 1
        if _first_drop is None:
            _first_drop = rec.t1


class _NoSpan:
    __slots__ = ()

    def __enter__(self) -> "_NoSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> None:
        pass


NO_SPAN = _NoSpan()


class Timed:
    """A span that always stamps ``t0``/``t1`` (for callers that keep the
    duration themselves) and records only while a profiler session ran
    when it was opened."""

    __slots__ = ("name", "attrs", "t0", "t1", "_on", "_ann", "_sid", "_parent")

    def __init__(self, name: str, attrs: dict, on: bool):
        self.name, self.attrs, self._on = name, attrs, on
        self.t0 = self.t1 = 0.0

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0

    def set(self, **attrs: Any) -> None:
        self.attrs.update(attrs)

    def __enter__(self) -> "Timed":
        if self._on:
            self._ann = TraceAnnotation(self.name)
            self._ann.__enter__()
            stack = _stack()
            self._parent = stack[-1] if stack else None
            self._sid = next(_ids)
            stack.append(self._sid)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc: Any) -> bool:
        self.t1 = time.monotonic()
        if self._on:
            _stack().pop()
            self._ann.__exit__(*exc)
            _keep(Span(self._sid, self.name, self.t0, self.t1, self._parent,
                       threading.get_ident(), self.attrs))
        return False


def span(name: str, **attrs: Any) -> Timed | _NoSpan:
    """A span named ``name``; the shared no-op while no profiler runs."""
    if not TraceAnnotation.is_enabled():
        return NO_SPAN
    return Timed(name, attrs, True)


def timed(name: str, **attrs: Any) -> Timed:
    """A span whose ``t0``/``t1`` are stamped whether or not a profiler
    runs, so a caller's own timing and the span share one pair of stamps."""
    return Timed(name, attrs, TraceAnnotation.is_enabled())


def records() -> Log:
    """The spans kept so far, in the order they closed, and what was
    dropped."""
    with _lock:
        return Log(list(_spans), _dropped, _first_drop)


def clear() -> None:
    global _dropped, _first_drop
    with _lock:
        _spans.clear()
        _dropped = 0
        _first_drop = None
