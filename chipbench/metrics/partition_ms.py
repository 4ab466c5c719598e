"""Host partitioning per solve: the self time of the program's
``repro.prep.partition`` spans (cutting X, y into the P x Q blocks; less
the ``repro.prep.transfer`` spans inside them, such as the dense path's
send of X), summed over each traced solve, mean over the traced solves."""
from __future__ import annotations

from chipbench import program_spans as ps


def read(ctx):
    per_solve = []
    for span, _ in ctx.solves:
        cuts = ps.events(ctx.trace, span.start, span.end,
                         "repro.prep.partition")
        if cuts:
            sends = ps.events(ctx.trace, span.start, span.end,
                              "repro.prep.transfer")
            per_solve.append(sum(ps.self_time(c, sends) for c in cuts))
    if not per_solve:
        return None
    return sum(per_solve) / len(per_solve) * 1e-6
