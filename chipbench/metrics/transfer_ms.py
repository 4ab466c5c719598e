"""Host-to-device sends per solve: the program's ``repro.prep.transfer``
spans (each put of the blocks, labels, mask and host starting iterates)
summed over each traced solve, mean over the traced solves."""
from __future__ import annotations

from chipbench import program_spans as ps


def read(ctx):
    per_solve = []
    for span, _ in ctx.solves:
        sends = ps.events(ctx.trace, span.start, span.end,
                          "repro.prep.transfer")
        if sends:
            per_solve.append(sum(e.duration for e in sends))
    if not per_solve:
        return None
    return sum(per_solve) / len(per_solve) * 1e-6
