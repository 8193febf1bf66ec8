"""What one run leaves for the per-layer metric readers, and the traced
slice of its window."""
from __future__ import annotations

import dataclasses
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import jax

from metrics.common import trace as trace_mod

# the traced slice: this many seconds from the middle of the window (the
# profiler records every operation of every scanned layer: a serve step
# gives about a thousand events)
TRACE_SECONDS = 2.0


@dataclasses.dataclass
class Span:
    name: str                     # "admit_step", "decode_step", "fold_step"
    start: float                  # host clock, seconds
    end: float
    attrs: Dict = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Run:
    """Read by ``metrics/<name>.py``: ``read(run) -> float | None``."""

    config: dict
    peaks: Dict[str, float]
    chips: int
    spans: List[Span]
    counters: Dict[str, float]
    trace: Optional[trace_mod.Trace] = None
    traced: Optional[Tuple[float, float]] = None   # host-clock slice

    def untraced(self, name: str) -> List[Span]:
        """Spans called ``name`` outside the traced slice, whose timing the
        profiler did not slow."""
        lo, hi = self.traced if self.traced else (0.0, 0.0)
        return [s for s in self.spans
                if s.name == name and (s.end <= lo or s.start >= hi)]


class WindowTracer:
    """Profiles TRACE_SECONDS from the middle of a window of ``seconds``
    (the whole window where it is shorter), when ``enabled``."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        span = min(TRACE_SECONDS, seconds)
        self.lo = (seconds - span) / 2
        self.hi = self.lo + span
        self.dir: Optional[str] = None
        self._ann = None
        self.host: Optional[Tuple[float, float]] = None
        self.stop_s = 0.0             # host time the profiler took to stop
        self._t0 = 0.0

    def poll(self, now: float, t0: float) -> None:
        """Start or stop the profiler as the window's clock passes."""
        if not self.enabled:
            return
        if self.dir is None and now - t0 >= self.lo:
            self.dir = tempfile.mkdtemp(prefix="bench_trace_")
            jax.profiler.start_trace(self.dir)
            self._ann = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            self._ann.__enter__()
            self._t0 = now
        elif self._ann is not None and now - t0 >= self.hi:
            self._stop(now)

    def _stop(self, now: float) -> None:
        self._ann.__exit__(None, None, None)
        self._ann = None
        t = time.perf_counter()
        jax.profiler.stop_trace()
        self.stop_s = time.perf_counter() - t
        self.host = (self._t0, now)

    def finish(self, now: float) -> Optional[trace_mod.Trace]:
        """Stop if still running; the reduced trace, its files removed."""
        if self._ann is not None:
            self._stop(now)
        if self.dir is None:
            return None
        try:
            return trace_mod.from_planes(
                trace_mod.planes_of(trace_mod.find_xplane(self.dir)))
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
