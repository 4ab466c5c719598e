"""Dense synthetic SVM data, the paper's section IV recipe (Part 1).

"the x_i's and w were sampled from the [-1,1] uniform distribution;
 y_i = sgn(w^T x_i), and the sign of each y_i was randomly flipped with
 probability 0.1.  The features were standardized to have unit variance."

A copy of ``repro.data.synthetic.make_svm_data``.
"""
from __future__ import annotations

import numpy as np


def make_dense(n: int, m: int, *, seed: int, flip: float = 0.1):
    """Returns ``(X (n, m) float32, y (n,) float32 in {-1, +1})``."""
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1.0, 1.0, size=(n, m))
    w = rng.uniform(-1.0, 1.0, size=(m,))
    y = np.sign(X @ w)
    y[y == 0] = 1.0
    flips = rng.random(n) < flip
    y = np.where(flips, -y, y)
    X = X / X.std(axis=0, keepdims=True)
    return X.astype(np.float32), y.astype(np.float32)
