"""Work an algorithm requires, counted from shapes and nonzeros.

What is counted is what the algorithm needs, not what today's layout
moves, so a layout that moves less raises a roofline share and cannot
push it past 100%.  Rows are float32 values (and int32 column ids when
sparse); a local epoch draws ``steps`` rows uniformly with replacement,
and only the distinct rows have to come from memory.
"""
from __future__ import annotations

F32 = 4
I32 = 4


def distinct_rows(n_p: int, steps: int) -> float:
    """Expected number of distinct rows among ``steps`` uniform draws
    from ``n_p``."""
    if n_p <= 0:
        return 0.0
    return n_p * (1.0 - (1.0 - 1.0 / n_p) ** steps)


def least_seconds(ops: float, nbytes: float, peaks: dict):
    """The least time the chip could take, and what bounds it."""
    t_ops = ops / peaks["bf16_flops_per_s"]
    t_mem = nbytes / peaks["hbm_bytes_per_s"]
    return (t_mem, "memory") if t_mem >= t_ops else (t_ops, "compute")
