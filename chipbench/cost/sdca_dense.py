"""One local SDCA epoch (Algorithm 2) on a dense (n_p, m_q) block."""
from __future__ import annotations

from chipbench.cost import F32, distinct_rows


def epoch(n_p: int, m_q: int, steps: int):
    """``(ops, bytes)`` the epoch requires.

    Each step takes the margin x_i . w (2 m_q) and updates w += c x_i
    (2 m_q).  From memory: every distinct row drawn once, ``w``'s block
    in and out, and four per-row vectors (labels, mask, the dual in, its
    delta out)."""
    ops = 4.0 * steps * m_q
    nbytes = F32 * (distinct_rows(n_p, steps) * m_q + 2 * m_q + 4 * n_p)
    return ops, nbytes
