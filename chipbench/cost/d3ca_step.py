"""Operations one D3CA outer iteration (Algorithm 1) requires."""
from __future__ import annotations


def outer_iteration(n: int, m: int, nnz: int, Q: int,
                    epochs: float = 1.0) -> float:
    """Local epochs in every cell (4 per nonzero, on average, for each
    pass of n_p steps; ``epochs`` = steps / n_p), the dual average over
    the Q feature blocks (n Q), the primal-dual map X^T alpha (2 per
    nonzero), and the duality gap that stops the solve: X w for the
    primal (2 per nonzero, plus the hinge and |w|^2) and the dual's
    X^T alpha (2 per nonzero)."""
    return 4.0 * nnz * epochs + n * Q + 2.0 * nnz \
        + (2.0 * nnz + 3 * n + 2 * m) + (2.0 * nnz + 2 * n + 2 * m)
