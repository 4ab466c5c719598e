"""One local SDCA epoch (Algorithm 2) on a sparse block of n_p rows,
m_q columns and ``nnz`` useful nonzeros (not the padded ELL width)."""
from __future__ import annotations

from chipbench.cost import F32, I32, distinct_rows


def epoch(n_p: int, m_q: int, steps: int, nnz: int):
    """``(ops, bytes)`` the epoch requires.

    A step on row i gathers its margin (2 nnz_i) and scatters the update
    (2 nnz_i); rows are drawn uniformly, so a step meets nnz / n_p
    nonzeros on average.  From memory: the values and column ids of every
    distinct row drawn, ``w``'s block in and out, and four per-row
    vectors (labels, mask, the dual in, its delta out)."""
    per_row = nnz / n_p if n_p else 0.0
    ops = 4.0 * steps * per_row
    nbytes = ((F32 + I32) * distinct_rows(n_p, steps) * per_row
              + F32 * (2 * m_q + 4 * n_p))
    return ops, nbytes
