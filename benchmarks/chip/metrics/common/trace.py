"""Reduce a profiler trace to what the per-layer metrics read.

A trace is read into plain intervals: for each TPU device its operations
(the ``XLA Ops`` line) and its programs (the ``XLA Modules`` line), and the
harness's own host spans (``jax.profiler.TraceAnnotation`` names starting
with ``bench.``).  The window is the host span ``bench.window``.  Times are
nanoseconds on the trace's clock.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

Interval = Tuple[float, float, str]          # (start_ns, end_ns, name)

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|collective-permute|all-to-all"
    r"|allreduce|allgather|reducescatter")


@dataclasses.dataclass
class Device:
    name: str
    ops: List[Interval]
    modules: List[Interval]


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    devices: List[Device]
    spans: List[Interval]                    # harness host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def from_planes(planes: Sequence[dict]) -> Trace:
    """Build from ``[{"name", "lines": [{"name", "events": [[name, start_ns,
    duration_ns], ...]}]}]`` -- the form :func:`planes_of` writes."""
    devices, spans = [], []
    for plane in planes:
        lines = {ln["name"]: ln["events"] for ln in plane["lines"]}
        if DEVICE_PLANE.match(plane["name"]):
            devices.append(Device(
                plane["name"],
                sorted(_intervals(lines.get(OPS_LINE, ()))),
                sorted(_intervals(lines.get(MODULES_LINE, ())))))
        else:
            for events in lines.values():
                spans.extend(iv for iv in _intervals(events)
                             if iv[2].startswith(SPAN_PREFIX))
    windows = [s for s in spans if s[2] == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in the trace")
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    devices.sort(key=lambda d: int(d.name.rsplit(":", 1)[1]))
    return Trace(window=windows[0][:2], devices=devices, spans=sorted(spans))


def _intervals(events: Iterable) -> List[Interval]:
    return [(float(s), float(s) + float(d), str(n)) for n, s, d in events]


def planes_of(path: str) -> List[dict]:
    """The device planes and the harness's host spans of an ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = []
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            events = [[op_name(e.name), e.start_ns, e.duration_ns]
                      for e in line.events
                      if device or e.name.startswith(SPAN_PREFIX)]
            if events:
                lines.append({"name": line.name, "events": events})
        if lines:
            out.append({"name": plane.name, "lines": lines})
    return out


def op_name(text: str) -> str:
    """``%fusion.91 = (bf16[...]) fusion(...)`` -> ``fusion.91``: a TPU op
    event is named by its whole HLO instruction."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(directory: str) -> str:
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def union(intervals: Iterable[Interval], lo: float,
          hi: float) -> List[Tuple[float, float]]:
    """Merged [start, end) pieces of ``intervals`` clipped to [lo, hi)."""
    out: List[List[float]] = []
    for s, e, _ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(pieces: Iterable[Tuple[float, float]]) -> float:
    return sum(e - s for s, e in pieces)


def subtract(a: List[Tuple[float, float]],
             b: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Pieces of ``a`` not covered by ``b`` (both merged and sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def is_collective(name: str) -> bool:
    return bool(COLLECTIVE.search(name.lower()))


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def busy_s(t: Trace) -> float:
    """Seconds in the window in which some operation ran, mean over devices."""
    lo, hi = t.window
    return sum(length(union(d.ops, lo, hi)) for d in t.devices) \
        * 1e-9 / len(t.devices)


def idle_share(t: Trace) -> float:
    return 1.0 - busy_s(t) / t.window_s


def collective_exposed_share(t: Trace) -> float:
    """Share of the window in which a collective ran and nothing else did on
    that device, mean over devices."""
    lo, hi = t.window
    total = 0.0
    for d in t.devices:
        coll = union([o for o in d.ops if is_collective(o[2])], lo, hi)
        other = union([o for o in d.ops if not is_collective(o[2])], lo, hi)
        total += length(subtract(coll, other))
    return total * 1e-9 / len(t.devices) / t.window_s


def module_runs(d: Device, pattern: str, lo: float,
                hi: float) -> List[Interval]:
    """Runs of the programs whose name contains ``pattern`` that lie wholly
    inside [lo, hi)."""
    return [m for m in d.modules if pattern in m[2] and m[0] >= lo
            and m[1] <= hi]


def op_seconds_in(d: Device, runs: Sequence[Interval], *,
                  collectives: bool) -> float:
    """Seconds inside ``runs`` in which an op (collective or not) ran: the
    union of their intervals, so an op nested in another (a loop and its
    body) counts once."""
    total, j = 0.0, 0
    ops = d.ops
    for s, e, _ in sorted(runs):
        while j < len(ops) and ops[j][0] < s:
            j += 1
        k = j
        inside = []
        while k < len(ops) and ops[k][0] < e:
            if is_collective(ops[k][2]) == collectives:
                inside.append(ops[k])
            k += 1
        total += length(union(inside, s, e))
    return total * 1e-9


def top_ops(t: Trace, n: int = 10) -> List[list]:
    """The ``n`` operation names with the most device time in the window,
    [name, seconds] with seconds summed over devices / devices."""
    lo, hi = t.window
    acc: Dict[str, float] = {}
    for d in t.devices:
        for s, e, name in d.ops:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                acc[name] = acc.get(name, 0.0) + (e - s)
    k = len(t.devices)
    top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns * 1e-9 / k] for name, ns in top]


def idle_gaps(t: Trace, n: int = 10) -> List[list]:
    """The ``n`` longest idle gaps of the first device, each named by the
    innermost harness span that covers its middle: [span, seconds]."""
    lo, hi = t.window
    d = t.devices[0]
    gaps = subtract([(lo, hi)], union(d.ops, lo, hi))
    gaps.sort(key=lambda g: g[0] - g[1])
    return [[covering_span(t.spans, (s + e) / 2), (e - s) * 1e-9]
            for s, e in gaps[:n]]


def covering_span(spans: Sequence[Interval], at: float) -> str:
    best: Optional[Interval] = None
    for s in spans:
        if s[0] <= at < s[1] and (best is None
                                  or s[1] - s[0] < best[1] - best[0]):
            best = s
    return best[2] if best is not None else "none"
