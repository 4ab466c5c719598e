"""Data preparation per solve: from the start of the benchmark's span
around ``Solver.solve`` to the first device op of that solve's first
outer step (partitioning on the host, transfer, program binding), mean
over the traced solves."""
from __future__ import annotations

from chipbench import trace_reduce as tr

#: the program's jitted outer step, as its XLA module is named in the
#: trace (``jit_step`` on the vmapped grid, ``jit_step_fn`` on a mesh)
STEP_MODULES = (r"^jit_step(_fn)?(\(|$)",)


def read(ctx):
    preps = []
    for span, _ in ctx.solves:
        firsts = [m.start for dev in ctx.trace.devices[:ctx.chips]
                  for m in tr.within(dev.modules, span.start, span.end)
                  if tr.matches(m, STEP_MODULES)]
        if firsts:
            preps.append(min(firsts) - span.start)
    if not preps:
        return None
    return sum(preps) / len(preps) * 1e-6
