"""Collective time left exposed per outer step: the time collective ops
run on a chip while no other op runs there (ops that only hold others,
such as a while loop, do not count as running), summed over the traced
window, divided by the outer steps, mean over the chips."""
from __future__ import annotations

from chipbench import trace_reduce as tr

#: collective ops, by the op kind in their HLO text (``%psum.12 = f32[..]
#: all-reduce(...)``; async pairs as ``all-reduce-start`` / ``-done``)
COLLECTIVES = (r" (all-reduce|all-gather|reduce-scatter|collective-permute"
               r"|all-to-all)(-start|-done)?\(",)


def read(ctx):
    lo, hi = ctx.window
    devs = ctx.trace.devices[:ctx.chips]
    exposed, found = 0.0, False
    for dev in devs:
        ops = tr.within(dev.ops, lo, hi)
        coll = [e for e in ops if tr.matches(e, COLLECTIVES)]
        found = found or bool(coll)
        other = [e for e in ops if not tr.matches(e, COLLECTIVES)
                 and e.kind not in tr.CONTAINER_KINDS]
        exposed += tr.uncovered(tr.spans(coll), tr.spans(other))
    if not found or not ctx.iters:
        return None
    return exposed / len(devs) / ctx.iters * 1e-6
