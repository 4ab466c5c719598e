"""Device-idle time between consecutive outer steps of one solve, summed
over the traced solves and divided by their steps, mean over the chips:
what the host's work between two steps (observing the objective and the
gap, dispatching the next step) costs the device."""
from __future__ import annotations

from chipbench import trace_reduce as tr

#: the program's jitted outer step, as its XLA module is named in the
#: trace (``jit_step`` on the vmapped grid, ``jit_step_fn`` on a mesh)
STEP_MODULES = (r"^jit_step(_fn)?(\(|$)",)


def read(ctx):
    idle, steps = 0.0, 0
    for dev in ctx.trace.devices[:ctx.chips]:
        busy = tr.union(tr.spans(dev.ops))
        for span, _ in ctx.solves:
            mods = [m for m in tr.within(dev.modules, span.start, span.end)
                    if tr.matches(m, STEP_MODULES)]
            for a, b in zip(mods, mods[1:]):
                idle += sum(e - s for s, e in tr.bare(busy, a.end, b.start))
            steps += len(mods)
    if not steps:
        return None
    return idle / steps * 1e-6
