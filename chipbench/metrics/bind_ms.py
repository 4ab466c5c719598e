"""Program binding per solve: the program's ``repro.prep.bind`` spans
(program-cache lookup, shape probes, jit wrappers, starting state)
summed over each traced solve, mean over the traced solves."""
from __future__ import annotations

from chipbench import program_spans as ps


def read(ctx):
    per_solve = []
    for span, _ in ctx.solves:
        binds = ps.events(ctx.trace, span.start, span.end, "repro.prep.bind")
        if binds:
            per_solve.append(sum(e.duration for e in binds))
    if not per_solve:
        return None
    return sum(per_solve) / len(per_solve) * 1e-6
