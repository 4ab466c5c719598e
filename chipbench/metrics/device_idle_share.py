"""Share of the traced window in which no op runs on a chip, mean over
the chips the cell uses: 1 - |union of device-op intervals| / window."""
from __future__ import annotations

from chipbench import trace_reduce as tr


def read(ctx):
    lo, hi = ctx.window
    devs = ctx.trace.devices[:ctx.chips]
    if not devs or hi <= lo:
        return None
    busy = [tr.length(tr.clip(tr.spans(d.ops), lo, hi)) for d in devs]
    return 100.0 * (1.0 - sum(busy) / len(devs) / (hi - lo))
