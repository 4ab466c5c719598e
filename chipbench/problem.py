"""A configuration's problem: its data made from the seed, and the form
the program under test takes it in.

A configuration file names its generator under ``data.generator``
(``"dense"`` or ``"sparse"``, the modules of :mod:`chipbench.gen`) with
that generator's keyword arguments beside it.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from chipbench.gen.dense import make_dense
from chipbench.gen.sparse import make_sparse

#: seeds of numpy's generator and of the program's PRNG keys are taken
#: modulo these, so that any whole number can be a benchmark seed
NUMPY_SEED_MOD = 2 ** 63
KEY_SEED_MOD = 2 ** 31


@dataclasses.dataclass
class Problem:
    n: int
    m: int
    lam: float
    y: np.ndarray
    #: (n, m) float32, or None for a CSR problem
    dense: Optional[np.ndarray] = None
    indptr: Optional[np.ndarray] = None
    indices: Optional[np.ndarray] = None
    data: Optional[np.ndarray] = None

    @property
    def nnz(self) -> int:
        return (self.n * self.m if self.dense is not None
                else int(self.indices.shape[0]))

    @property
    def row_ids(self) -> np.ndarray:
        ids = getattr(self, "_row_ids", None)
        if ids is None:
            ids = np.repeat(np.arange(self.n, dtype=np.int64),
                            np.diff(self.indptr))
            self._row_ids = ids
        return ids

    def cell_nnz(self, P: int, Q: int) -> np.ndarray:
        """(P, Q) nonzeros of each block of a CSR problem's P x Q grid,
        blocks cut as the paper cuts them: ceil(n / P) rows and
        ceil(m / Q) columns."""
        n_p, m_q = -(-self.n // P), -(-self.m // Q)
        flat = (self.row_ids // n_p) * Q + self.indices // m_q
        return np.bincount(flat, minlength=P * Q).reshape(P, Q)


def make_problem(config: dict, seed: int) -> Problem:
    """The configuration's data, made from ``seed``."""
    spec = dict(config["data"])
    kind = spec.pop("generator")
    seed = int(seed) % NUMPY_SEED_MOD
    lam = float(config["lam"])
    if kind == "dense":
        X, y = make_dense(spec["n"], spec["m"], seed=seed)
        return Problem(spec["n"], spec["m"], lam, y, dense=X)
    if kind == "sparse":
        indptr, indices, data, y = make_sparse(
            spec["n"], spec["m"], density=spec["density"], seed=seed)
        return Problem(spec["n"], spec["m"], lam, y, indptr=indptr,
                       indices=indices, data=data)
    raise ValueError(f"unknown generator {kind!r}")


def program_input(problem: Problem):
    """``X`` as the program's ``Solver.solve`` takes it: the dense array,
    or the program's own CSR container."""
    if problem.dense is not None:
        return problem.dense
    from repro.data.sparse import CSRMatrix
    return CSRMatrix(indptr=problem.indptr, indices=problem.indices,
                     data=problem.data, shape=(problem.n, problem.m))
