"""The program's own spans in a reduced trace, and where device idle lies
among them.

The solver writes its spans into the profiler's trace as
``jax.profiler.TraceAnnotation`` s named ``repro.*`` (``repro.solve``,
``repro.prep.partition``, ``repro.observe``, ...), on the thread that
calls it: the thread that writes the benchmark's ``chipbench.`` spans,
whose events :func:`chipbench.trace_reduce.from_profile` keeps in
``Trace.host``.  It keeps them without their arguments (the counters
the program attaches), so what is read here is names and times.

A thread's spans nest, so each instant of a solve lies in one innermost
span; :func:`innermost` cuts the time they cover into pieces labelled
with it, and :func:`idle_by_span` sums a device's idle time by that
label.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional, Sequence, Tuple

from chipbench import trace_reduce as tr

#: names of the spans the program writes
PREFIX = "repro."

Piece = Tuple[float, float, tr.Event]


def events(trace, lo: float = float("-inf"), hi: float = float("inf"),
           name: Optional[str] = None) -> List[tr.Event]:
    """The program's spans that start inside [lo, hi), sorted by start;
    only those called ``name`` where given."""
    return [e for e in tr.within(trace.host, lo, hi)
            if e.name.startswith(PREFIX) and (name is None or e.name == name)]


def innermost(spans: Sequence[tr.Event]) -> List[Piece]:
    """Sorted disjoint ``(start, end, span)`` pieces covering the union of
    ``spans`` (one thread's, so properly nested), each labelled with the
    innermost span that covers it."""
    out: List[Piece] = []
    stack: List[tr.Event] = []
    cursor = float("-inf")

    def close_until(t: float):
        # pop the spans that end by t, emitting each one's uncovered tail
        nonlocal cursor
        while stack and stack[-1].end <= t:
            top = stack.pop()
            if top.end > cursor:
                out.append((cursor, top.end, top))
                cursor = top.end

    for span in sorted(spans, key=lambda e: (e.start, -e.end)):
        close_until(span.start)
        if stack and span.start > cursor:
            out.append((cursor, span.start, stack[-1]))
        cursor = max(cursor, span.start)
        stack.append(span)
    close_until(float("inf"))
    return [(s, e, span) for s, e, span in out if e > s]


def covering(pieces: Sequence[Piece], lo: float, hi: float
             ) -> Dict[Optional[str], float]:
    """How much of [lo, hi] each innermost span covers, by span name; the
    part no span covers under None.  ``pieces`` as :func:`innermost`
    gives them (disjoint, so sorted by their ends too)."""
    out: Dict[Optional[str], float] = {}
    cursor = lo
    first = bisect.bisect_right(pieces, lo, key=lambda p: p[1])
    for s, e, span in pieces[first:]:
        if s >= hi:
            break
        a, b = max(s, lo), min(e, hi)
        if a > cursor:
            out[None] = out.get(None, 0.0) + a - cursor
        out[span.name] = out.get(span.name, 0.0) + b - a
        cursor = b
    if hi > cursor:
        out[None] = out.get(None, 0.0) + hi - cursor
    return out


def idle_by_span(trace, device, windows: Sequence[Tuple[float, float]]
                 ) -> Dict[Optional[str], float]:
    """The device's idle time (ns) inside ``windows``, summed by the
    innermost program span the host was in (None: in none)."""
    busy = tr.union(tr.spans(device.ops))
    pieces = innermost(events(trace))
    out: Dict[Optional[str], float] = {}
    for lo, hi in windows:
        for a, b in tr.bare(busy, lo, hi):
            for name, ns in covering(pieces, a, b).items():
                out[name] = out.get(name, 0.0) + ns
    return out


def self_time(span: tr.Event, children: Sequence[tr.Event]) -> float:
    """``span``'s duration less the part of it ``children`` cover."""
    return span.duration - tr.length(tr.clip(tr.spans(children),
                                             span.start, span.end))
