"""The sparse SDCA kernel's share of its roofline: the least time the
chip needs for the epochs' required ops and bytes, counted from each
block's useful nonzeros (``chipbench.cost.sdca_sparse``, at
``peaks.json``), over the kernel's summed device time in the traced
window."""
from __future__ import annotations

from chipbench import trace_reduce as tr
from chipbench.cost import least_seconds
from chipbench.cost.sdca_sparse import epoch

#: how the kernel is found in the trace: the op text of a Mosaic kernel.
#: Pallas kernels carry no name of their own there today (each is a
#: ``closed_call`` custom call), and this cell runs no other
KERNELS = (r'custom_call_target="tpu_custom_call"',)


def read(ctx):
    lo, hi = ctx.window
    kernel_ns = sum(e.duration for dev in ctx.trace.devices[:ctx.chips]
                    for e in tr.within(dev.ops, lo, hi)
                    if tr.matches(e, KERNELS))
    if kernel_ns <= 0 or not ctx.iters:
        return None
    P, Q = ctx.grid
    n, m = ctx.problem.n, ctx.problem.m
    n_p, m_q = -(-n // P), -(-m // Q)
    least, bounds = 0.0, set()
    for nnz in ctx.problem.cell_nnz(P, Q).ravel():
        secs, bound = least_seconds(*epoch(n_p, m_q, ctx.steps, int(nnz)),
                                    ctx.peaks)
        least += secs
        bounds.add(bound)
    least *= ctx.iters
    return {"value": 100.0 * least / (kernel_ns * 1e-9),
            "note": {"bound": "/".join(sorted(bounds)), "least_s": least,
                     "kernel_s": kernel_ns * 1e-9}}
