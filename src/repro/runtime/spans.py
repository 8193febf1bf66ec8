"""In-memory span recorder for the serving engine's layer boundaries.

One module-level recorder, off by default.  ``span(name, **attrs)`` wraps a
piece of engine work; while the recorder is off it is one flag check that
returns a shared no-op, with no clock read and no profiler annotation.
Between :func:`start` and :func:`stop` every span is kept in memory as a
:class:`Span` (name, start, end, parent, attrs) and also enters
``jax.profiler.TraceAnnotation("repro." + name)``, so inside a profiler
trace the same span lands on the trace's host plane, on the device
trace's clock.

Times are seconds on the clock given to :func:`start`.  Pass the engine's
own clock (``time.perf_counter`` unless one was injected) so that spans
and ``StreamEvent.time_s`` share one clock.

A span's parent is the span that enclosed it.  Spans marked ``wait=True``
are the host blocked on the device (``jax.device_get`` or ``np.asarray``
of a device array).  Spans about one request carry its ``uid``; admission
spans carry the ``uids`` they admitted.  Counters are not kept here: they
stay in ``EngineStats``, ``PrefixCacheStats`` and ``BatcherStats``.

The recorder keeps one stack of open spans and is meant for the thread
that drives the engine.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, List, Optional

import jax

PREFIX = "repro."


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: Optional[float]              # None while the span is open
    parent: Optional[int]             # index of the enclosing span
    attrs: Dict[str, Any]
    wait: bool = False                # the host blocked on the device

    @property
    def seconds(self) -> float:
        return self.end - self.start


class _NoSpan:
    """What :func:`span` returns while the recorder is off."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return None

    def end_at(self, t: float) -> float:
        return t


_NO_SPAN = _NoSpan()

_on = False
_clock: Callable[[], float] = time.perf_counter
_spans: List[Span] = []
_stack: List[int] = []


class _OpenSpan:
    __slots__ = ("_span", "_ann", "_end")

    def __init__(self, name: str, start: Optional[float], wait: bool,
                 attrs: Dict[str, Any]):
        self._span = Span(name, start, None, None, attrs, wait)
        self._ann = jax.profiler.TraceAnnotation(PREFIX + name)
        self._end: Optional[float] = None

    def __enter__(self):
        sp = self._span
        if sp.start is None:
            sp.start = _clock()
        sp.parent = _stack[-1] if _stack else None
        _stack.append(len(_spans))
        _spans.append(sp)
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        end = _clock() if self._end is None else self._end
        self._ann.__exit__(*exc)
        self._span.end = end
        _stack.pop()
        return None

    def end_at(self, t: float) -> float:
        """Close the span at ``t`` (a reading the caller took) instead of at
        its exit; returns ``t``."""
        self._end = t
        return t


def span(name: str, *, start: Optional[float] = None, wait: bool = False,
         **attrs):
    """Context manager recording ``name`` around its block.  ``start``
    opens the span at a reading the caller already took."""
    if not _on:
        return _NO_SPAN
    return _OpenSpan(name, start, wait, attrs)


def add(name: str, start: float, end: float, **attrs) -> None:
    """Record a span after the fact, such as a request's wait in the queue.
    It has no parent: it was not nested in the work that recorded it."""
    if _on:
        _spans.append(Span(name, start, end, None, attrs))


def recording() -> bool:
    return _on


def start(clock: Callable[[], float] = time.perf_counter) -> None:
    """Start keeping spans, timed by ``clock``."""
    global _on, _clock
    if _on:
        raise RuntimeError("the span recorder is already recording")
    _spans.clear()
    _stack.clear()
    _clock = clock
    _on = True


def stop() -> List[Span]:
    """Stop keeping spans and return them, in the order they opened.  Call
    it outside any span."""
    global _on
    if _stack:
        raise RuntimeError(f"{len(_stack)} spans are still open")
    _on = False
    out = list(_spans)
    _spans.clear()
    return out

