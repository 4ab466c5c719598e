"""Outer iterations a solve of the traced window took to reach the gap
target (``SolveResult.iters``), mean over the window's solves."""
from __future__ import annotations


def read(ctx):
    if not ctx.solves:
        return None
    return sum(r.iters for _, r in ctx.solves) / len(ctx.solves)
