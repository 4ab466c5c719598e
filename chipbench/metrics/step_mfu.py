"""The whole solve's share of the chips' bf16 peak: the operations D3CA
requires per outer iteration (``chipbench.cost.d3ca_step``) times the
iterations in the traced window, over the window times the chips times
the peak."""
from __future__ import annotations

from chipbench.cost.d3ca_step import outer_iteration


def read(ctx):
    if not ctx.iters or ctx.window_s <= 0:
        return None
    p = ctx.problem
    n_p = -(-p.n // ctx.grid[0])
    ops = outer_iteration(p.n, p.m, p.nnz, ctx.grid[1],
                          epochs=ctx.steps / n_p) * ctx.iters
    return 100.0 * ops / (ctx.window_s * ctx.chips
                          * ctx.peaks["bf16_flops_per_s"])
