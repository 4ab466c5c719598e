"""Observation holding back the next step, per outer iteration: the part
of each ``repro.observe`` span (objective and gap, with their waits and
``float()``) after its iteration's step module ended on the device, mean
over the traced iterations.  The note splits it into the device's busy
and idle time (mean over the chips) and gives the mean primal and dual
evaluations (``repro.observe.primal`` / ``.dual``), all in ms per
iteration."""
from __future__ import annotations

from chipbench import program_spans as ps
from chipbench import trace_reduce as tr
from chipbench.metrics.host_gap_ms import STEP_MODULES


def read(ctx):
    devs = ctx.trace.devices[:ctx.chips]
    if not devs:
        return None
    busy_u = [tr.union(tr.spans(d.ops)) for d in devs]
    held = busy = primal = dual = 0.0
    n = 0
    for span, _ in ctx.solves:
        # each outer iteration runs one step module, then one observation:
        # pair them in order (the device's clock in a profile can run
        # ahead of the host's by more than a dispatch takes, so the next
        # step may seem to start before an observation ends)
        steps = [[m for m in tr.within(d.modules, span.start, span.end)
                  if tr.matches(m, STEP_MODULES)] for d in devs]
        observes = ps.events(ctx.trace, span.start, span.end,
                             "repro.observe")
        for i, obs in enumerate(observes):
            lo = max([obs.start] + [mods[i].end for mods in steps
                                    if i < len(mods)])
            if obs.end > lo:
                held += obs.end - lo
                busy += sum(obs.end - lo - sum(b - a for a, b in
                                               tr.bare(u, lo, obs.end))
                            for u in busy_u) / len(devs)
            inner = ps.events(ctx.trace, obs.start, obs.end)
            primal += sum(e.duration for e in inner
                          if e.name == "repro.observe.primal")
            dual += sum(e.duration for e in inner
                        if e.name == "repro.observe.dual")
            n += 1
    if not n:
        return None
    ms = 1e-6 / n
    return {"value": held * ms,
            "note": {"device_busy_ms": busy * ms,
                     "device_idle_ms": (held - busy) * ms,
                     "primal_ms": primal * ms, "dual_ms": dual * ms}}
