"""Reduce a JAX profiler trace to device-op intervals and host spans.

``load(path)`` reads one ``.xplane.pb`` (``jax.profiler.ProfileData``) and
returns a :class:`Trace`: for every TPU device the intervals of its XLA
ops and of its XLA modules (one module event per program execution), and
the host spans the benchmark wrote with ``jax.profiler.TraceAnnotation``
(names starting with ``chipbench.``), plus the host events of the thread
that wrote them, for naming idle gaps.  All times are nanoseconds on the
profiler's one clock.

The interval arithmetic below (union, gaps, coverage) is what every
per-layer metric reads; it knows no cell, kernel or metric.
"""
from __future__ import annotations

import bisect
import dataclasses
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: plane names of the devices the reduction reads
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
#: line names on a device plane
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host spans written by the benchmark
ANNOTATION_PREFIX = "chipbench."
CUSTOM_CALL_TARGET = re.compile(r'custom_call_target="([^"]+)"')
#: the op kind in an HLO instruction's text: the first word that opens a
#: bracket after the result shape (``%x = f32[8]{0} all-reduce(...)``)
OP_KIND = re.compile(r" ([a-z][\w-]*)\(")
#: ops that only hold other ops, whose time their body's ops account for
CONTAINER_KINDS = ("while", "conditional", "call")

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float            # ns
    end: float              # ns
    stats: Dict[str, object] = dataclasses.field(default_factory=dict)

    @property
    def duration(self) -> float:
        return self.end - self.start

    @property
    def op(self) -> str:
        """A device op's HLO name (the event name is the op's whole HLO
        text), with its custom-call target when it has one."""
        name = self.name.split(" = ", 1)[0].lstrip("%")
        target = CUSTOM_CALL_TARGET.search(self.name)
        return f"{name} ({target.group(1)})" if target else name

    @property
    def kind(self) -> str:
        """A device op's HLO kind (``fusion``, ``while``, ``all-reduce``,
        ``custom-call``, ...), or "" when the name is no HLO text."""
        _, eq, rest = self.name.partition(" = ")
        found = OP_KIND.search(" " + rest) if eq else None
        return found.group(1) if found else ""


@dataclasses.dataclass
class Device:
    index: int
    ops: List[Event]
    modules: List[Event]


@dataclasses.dataclass
class Trace:
    devices: List[Device]
    annotations: List[Event]
    #: host events of the thread that wrote the annotations
    host: List[Event]

    def annotation(self, name: str) -> List[Event]:
        return [a for a in self.annotations if a.name == name]


def _event(e, with_stats: bool = False) -> Event:
    """One profiler event; its stats are read only where asked for (the
    benchmark's own spans), since reading them is most of a large
    trace's reduction."""
    start = float(e.start_ns)
    stats = {}
    if with_stats:
        for key, value in e.stats:
            if isinstance(value, (str, int, float)):
                stats[key] = value
    return Event(e.name, start, start + float(e.duration_ns), stats)


def from_profile(profile) -> Trace:
    """Build a :class:`Trace` from a ``jax.profiler.ProfileData``."""
    devices: List[Device] = []
    annotations: List[Event] = []
    host_lines: List[List[Event]] = []
    for plane in profile.planes:
        match = DEVICE_PLANE.match(plane.name)
        if match:
            ops, modules = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = sorted((_event(e) for e in line.events),
                                 key=lambda ev: ev.start)
                elif line.name == MODULES_LINE:
                    modules = sorted((_event(e) for e in line.events),
                                     key=lambda ev: ev.start)
            devices.append(Device(int(match.group(1)), ops, modules))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if not any(e.name.startswith(ANNOTATION_PREFIX)
                           for e in line.events):
                    continue
                events = [_event(e, e.name.startswith(ANNOTATION_PREFIX))
                          for e in line.events]
                annotations.extend(e for e in events
                                   if e.name.startswith(ANNOTATION_PREFIX))
                host_lines.append(sorted(events, key=lambda ev: ev.start))
    devices.sort(key=lambda d: d.index)
    annotations.sort(key=lambda ev: ev.start)
    host = [e for line in host_lines for e in line
            if not e.name.startswith(ANNOTATION_PREFIX)]
    host.sort(key=lambda ev: ev.start)
    return Trace(devices, annotations, host)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_profile(ProfileData.from_file(str(path)))


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of ``intervals`` inside [lo, hi]."""
    out = []
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out.append((s, e))
    return out


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Sorted disjoint intervals covering exactly the same points."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def length(intervals: Iterable[Interval]) -> float:
    """Length of the union of ``intervals``."""
    return sum(e - s for s, e in union(intervals))


def bare(cover_u: Sequence[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that ``cover_u``, sorted disjoint intervals
    as :func:`union` gives them, leaves bare; found by bisection, so a
    trace's many windows each cost their own length, not the trace's."""
    out, cursor = [], lo
    # the first interval that could reach past lo: the one before the
    # first that starts after lo
    i = max(bisect.bisect_right(cover_u, (lo, float("inf"))) - 1, 0)
    for s, e in cover_u[i:]:
        if s >= hi:
            break
        if e <= cursor:
            continue
        if s > cursor:
            out.append((cursor, s))
        cursor = max(cursor, e)
    if hi > cursor:
        out.append((cursor, hi))
    return out


def gaps(intervals: Iterable[Interval], lo: float, hi: float) -> List[Interval]:
    """The parts of [lo, hi] that no interval covers."""
    return bare(union(clip(intervals, lo, hi)), lo, hi)


def uncovered(intervals: Iterable[Interval],
              cover: Iterable[Interval]) -> float:
    """Length of the union of ``intervals`` that ``cover`` leaves bare."""
    cover_u = union(cover)
    return sum(b - a for s, e in union(intervals)
               for a, b in bare(cover_u, s, e))


def spans(events: Iterable[Event]) -> List[Interval]:
    return [(e.start, e.end) for e in events]


def matches(event: Event, patterns: Sequence[str]) -> bool:
    """True when any regex in ``patterns`` matches the event's name (on a
    TPU device: the op's whole HLO text)."""
    return any(re.search(p, event.name) for p in patterns)


def within(events: Sequence[Event], lo: float, hi: float) -> List[Event]:
    """Events that start inside [lo, hi), of ``events`` sorted by start
    (as :class:`Trace` holds them)."""
    key = lambda e: e.start  # noqa: E731
    return list(events[bisect.bisect_left(events, lo, key=key):
                       bisect.bisect_left(events, hi, key=key)])


def host_doing(trace: Trace, lo: float, hi: float) -> Optional[str]:
    """Name of the host event that covers most of [lo, hi] on the thread
    that wrote the benchmark's spans, or None."""
    best, best_cover = None, 0.0
    for e in trace.host:
        if e.start >= hi:
            break
        if e.end <= lo:
            continue
        cover = min(e.end, hi) - max(e.start, lo)
        if cover > best_cover:
            best, best_cover = e.name, cover
    return best
