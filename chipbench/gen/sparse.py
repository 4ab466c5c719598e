"""Sparse synthetic SVM data in CSR form, the paper's section IV recipe.

Uniform [-1, 1] entries and a planted ``w``, labels ``sgn(w^T x)`` with
10% of them flipped, unit-variance columns.  Each row holds
Binomial(m, density) nonzeros (at least one) at distinct sorted columns.
It follows ``repro.data.sparse.make_sparse_svm_csr`` and never builds the
dense matrix, with one change: the sparsity pattern (each row's size and
columns) comes from ``pattern_seed``, the same for every run, and the
run's seed draws the order of the rows, the values, the planted ``w``
and the label flips.  So every seed gives the program the same row sizes,
and the same padded-ELL width, in another order: the seed does not change
the work.
"""
from __future__ import annotations

import numpy as np


def make_pattern(n: int, m: int, *, density: float, seed: int):
    """``(counts (n,) int64, indices (nnz,) int32)``: each row's size and
    its distinct sorted columns, rows one after another."""
    rng = np.random.default_rng(seed)
    counts = np.maximum(rng.binomial(m, density, size=n), 1)
    ends = np.cumsum(counts)
    indices = np.empty((int(ends[-1]),), dtype=np.int32)
    for i in range(n):
        indices[ends[i] - counts[i]:ends[i]] = np.sort(
            rng.choice(m, size=counts[i], replace=False))
    return counts.astype(np.int64), indices


def make_sparse(n: int, m: int, *, density: float, seed: int,
                flip: float = 0.1, pattern_seed: int = 0):
    """Returns ``(indptr (n+1,) int64, indices (nnz,) int32, data (nnz,)
    float32, y (n,) float32 in {-1, +1})``."""
    counts, pattern = make_pattern(n, m, density=density, seed=pattern_seed)
    rng = np.random.default_rng(seed)
    # the pattern's rows in the order this seed draws
    order = rng.permutation(n)
    old_start = np.cumsum(counts) - counts
    counts = counts[order]
    indptr = np.zeros((n + 1,), dtype=np.int64)
    indptr[1:] = np.cumsum(counts)
    nnz = int(indptr[-1])
    rows = np.repeat(np.arange(n, dtype=np.int64), counts)
    indices = pattern[old_start[order][rows] + np.arange(nnz) - indptr[rows]]
    data = rng.uniform(-1.0, 1.0, size=nnz).astype(np.float32)

    w = rng.uniform(-1.0, 1.0, size=m).astype(np.float32)
    z = np.zeros((n,), dtype=np.float64)
    np.add.at(z, rows, data.astype(np.float64) * w[indices])
    y = np.sign(z)
    y[y == 0] = 1.0
    flips = rng.random(n) < flip
    y = np.where(flips, -y, y).astype(np.float32)

    # column std over all n entries (zeros included), population form
    s1 = np.zeros((m,), dtype=np.float64)
    s2 = np.zeros((m,), dtype=np.float64)
    np.add.at(s1, indices, data.astype(np.float64))
    np.add.at(s2, indices, data.astype(np.float64) ** 2)
    var = s2 / n - (s1 / n) ** 2
    std = np.sqrt(np.maximum(var, 0.0))
    std[std == 0] = 1.0
    data = (data / std[indices]).astype(np.float32)
    return indptr, indices, data, y
