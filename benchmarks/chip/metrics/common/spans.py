"""Per-layer readings from the program's own spans and counters.

The spans are those of the program's span recorder
(``repro.runtime.spans``): each has ``name``, ``start``, ``end`` (seconds on
the engine's clock, the harness's ``time.perf_counter``), ``parent`` (index
of the enclosing span or None), ``attrs`` and ``wait`` (the host blocked
on the device).  Host times are read only from spans that lie wholly
outside the profiled slice ``traced``, since the profiler slows the host.
The counters are the window's deltas of the engine's, the prefix cache's
and the admission queue's counters.
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple

Slice = Optional[Tuple[float, float]]


def untraced(spans: Sequence, traced: Slice) -> List[int]:
    """Indices of the spans that lie wholly outside the profiled slice."""
    if not traced:
        return list(range(len(spans)))
    lo, hi = traced
    return [i for i, sp in enumerate(spans) if sp.end <= lo or sp.start >= hi]


def descendants(spans: Sequence, i: int) -> List[int]:
    """Indices of the spans nested, at any depth, in span ``i``."""
    inside, out = {i}, []
    for j in range(i + 1, len(spans)):
        sp = spans[j]
        if sp.parent not in inside:
            # descendants open right after span i; the first span opened
            # outside it ends them (a span recorded after the fact has no
            # parent and may sit among them)
            if sp.parent is not None or sp.start >= spans[i].end:
                break
            continue
        inside.add(j)
        out.append(j)
    return out


def covered(spans: Sequence, i: int, keep: Callable) -> float:
    """Seconds of span ``i`` covered by its descendants for which ``keep``
    holds, each clipped to span ``i`` and overlaps counted once."""
    lo, hi = spans[i].start, spans[i].end
    pieces = sorted((max(spans[j].start, lo), min(spans[j].end, hi))
                    for j in descendants(spans, i) if keep(spans[j]))
    total, reach = 0.0, lo
    for s, e in pieces:
        s = max(s, reach)
        if e > s:
            total += e - s
            reach = e
    return total


def self_time(spans: Sequence, i: int) -> float:
    """Span ``i``'s length less what its children cover."""
    return spans[i].end - spans[i].start - covered(
        spans, i, lambda sp: sp.parent == i)


def host_time(spans: Sequence, i: int) -> float:
    """Span ``i``'s length less what its ``wait`` descendants cover: the
    host's own work inside it."""
    return spans[i].end - spans[i].start - covered(
        spans, i, lambda sp: sp.wait)


def _mean_ms(values: List[float]) -> Optional[float]:
    return sum(values) / len(values) * 1e3 if values else None


def queue_wait_ms(spans: Sequence, traced: Slice) -> Optional[float]:
    """Mean ``engine.queued`` length: a request's wait from submission to
    its admission."""
    return _mean_ms([spans[i].end - spans[i].start
                     for i in untraced(spans, traced)
                     if spans[i].name == "engine.queued"])


def admit_host_ms(spans: Sequence, traced: Slice) -> Optional[float]:
    """Mean host time of an ``engine.admit``: its length less its ``wait``
    spans."""
    return _mean_ms([host_time(spans, i) for i in untraced(spans, traced)
                     if spans[i].name == "engine.admit"])


def decode_host_ms(spans: Sequence, traced: Slice) -> Optional[float]:
    """Mean host time of an ``engine.decode`` (less its ``wait`` spans) in
    engine steps that admitted nothing."""
    admitting = {sp.parent for sp in spans if sp.name == "engine.admit"}
    return _mean_ms([host_time(spans, i) for i in untraced(spans, traced)
                     if spans[i].name == "engine.decode"
                     and spans[i].parent not in admitting])


def prefix_copy_mb(counters: Dict[str, float]) -> Optional[float]:
    """Prefix KV bytes sent to the device and fetched back per admitted
    request, in MB."""
    admitted = counters.get("admitted", 0)
    if admitted <= 0 or "prefix_upload_bytes" not in counters:
        return None
    return (counters["prefix_upload_bytes"]
            + counters["prefix_download_bytes"]) / admitted / 1e6


def decode_batch(counters: Dict[str, float]) -> Optional[float]:
    """Occupied slots per decode step."""
    steps = counters.get("steps", 0)
    if steps <= 0 or "slot_steps" not in counters:
        return None
    return counters["slot_steps"] / steps


def admission_split(spans: Sequence, traced: Slice) -> Dict[str, float]:
    """Mean milliseconds per untraced ``engine.admit`` of each span name
    nested in it (summed within an admission), and of its own self time
    under the name ``engine.admit``."""
    admits = [i for i in untraced(spans, traced)
              if spans[i].name == "engine.admit"]
    acc: Dict[str, float] = {}
    for i in admits:
        acc["engine.admit"] = acc.get("engine.admit", 0.0) \
            + self_time(spans, i)
        for j in descendants(spans, i):
            sp = spans[j]
            acc[sp.name] = acc.get(sp.name, 0.0) + sp.end - sp.start
    return {k: v / len(admits) * 1e3 for k, v in acc.items()}


def traced_admission(t, prefix: str = "repro.") -> Optional[dict]:
    """The longest ``repro.engine.admit`` host span of a reduced trace
    (whose spans keep the program's): the program spans inside it and the
    device programs (``XLA Modules``) of the first device that ran in it,
    each ``[name, count, ms]`` clipped to the admission."""
    admits = [s for s in t.spans if s[2] == prefix + "engine.admit"]
    if not admits:
        return None
    lo, hi, _ = max(admits, key=lambda s: s[1] - s[0])

    def total(intervals):
        acc: Dict[str, list] = {}
        for s, e, name in intervals:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                row = acc.setdefault(name, [name, 0, 0.0])
                row[1] += 1
                row[2] += (e - s) * 1e-6
        return sorted(acc.values(), key=lambda r: -r[2])

    return {"ms": (hi - lo) * 1e-6,
            "host": total(s for s in t.spans if s[2].startswith(prefix)
                          and lo <= s[0] and s[1] <= hi),
            "device": total(t.devices[0].modules)}
