"""Program spans: profiler annotations plus an in-memory record on the host clock.

``span(name, **attrs)`` marks a layer boundary of the program (the front
end, the dispatch rounds, the serve loop).  The JAX profiler is the one
switch:

* while no profiler session records, ``span`` costs one check and returns
  a shared no-op context;
* while ``jax.profiler`` records (``jax.profiler.trace`` /
  ``start_trace``), it writes a ``jax.profiler.TraceAnnotation`` named
  ``repro.<name>`` with ``attrs`` into the trace, beside the device ops,
  and appends a :class:`Span` to an in-memory list, timed on
  ``time.perf_counter``.

``Span.parent`` is the index (into :func:`spans`) of the span that was
open on the same thread when this one began, so self time can be
computed.  :func:`interval` appends an interval the program timed itself
(``LPEngine``'s per-ticket queueing and in-flight times), in memory
only.  The list belongs to the process, as the profiler session does;
:func:`clear` empties it.
"""

from __future__ import annotations

import threading
import time
from typing import Dict, List, NamedTuple, Optional

import jax

PREFIX = "repro."


class Span(NamedTuple):
    """One recorded span; ``t1`` is None while it is still open."""

    name: str
    t0: float
    t1: Optional[float]
    parent: Optional[int]
    attrs: Dict[str, object]


_is_enabled = getattr(jax.profiler.TraceAnnotation, "is_enabled", None)
_lock = threading.Lock()
_spans: List[Span] = []
_generation = 0  # bumped by clear(): a span opened before it is dropped
_open = threading.local()  # .stack: (index, generation) of this thread's open spans


def recording() -> bool:
    """Whether a profiler session records (False if jaxlib cannot say)."""
    return _is_enabled is not None and _is_enabled()


class _NoSpan:
    """The shared context ``span`` returns while nothing records."""

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **attrs) -> None:
        """Attributes known only inside the span; ignored here."""


_NO_SPAN = _NoSpan()


class _Recorded:
    """A span written to the profiler trace and to the in-memory list."""

    __slots__ = ("name", "attrs", "annotation", "index", "generation", "t0")

    def __init__(self, name: str, attrs: Dict[str, object]):
        self.name = name
        self.attrs = attrs
        self.annotation = jax.profiler.TraceAnnotation(PREFIX + name, **attrs)

    def __enter__(self):
        stack = getattr(_open, "stack", None)
        if stack is None:
            stack = _open.stack = []
        self.annotation.__enter__()
        self.t0 = time.perf_counter()
        with _lock:
            self.index = len(_spans)
            self.generation = _generation
            parent = stack[-1][0] if stack and stack[-1][1] == _generation else None
            _spans.append(Span(self.name, self.t0, None, parent, self.attrs))
        stack.append((self.index, self.generation))
        return self

    def __exit__(self, *exc):
        t1 = time.perf_counter()
        self.annotation.__exit__(*exc)
        _open.stack.pop()
        with _lock:
            if self.generation == _generation:
                _spans[self.index] = Span(self.name, self.t0, t1,
                                          _spans[self.index].parent, self.attrs)
        return False

    def set(self, **attrs) -> None:
        """Add attributes known only inside the span (a resolved backend, a ticket)."""
        self.attrs.update(attrs)
        self.annotation.set_metadata(**attrs)


def span(name: str, **attrs):
    """Context manager for one span named ``repro.<name>``; see the module docstring.

    The context it returns has ``set(**attrs)`` for attributes known
    only inside the span.
    """
    if not recording():
        return _NO_SPAN
    return _Recorded(name, dict(attrs))


def interval(name: str, t0: float, t1: float, **attrs) -> None:
    """Append an interval timed on ``time.perf_counter`` (in memory only, no parent)."""
    with _lock:
        _spans.append(Span(name, t0, t1, None, dict(attrs)))


def spans() -> List[Span]:
    """A copy of what was recorded since the last :func:`clear`.

    Spans are in the order they began; an :func:`interval` sits where it
    was appended.
    """
    with _lock:
        return list(_spans)


def clear() -> None:
    """Empty the list; spans still open are not recorded when they close."""
    global _generation
    with _lock:
        _spans.clear()
        _generation += 1
