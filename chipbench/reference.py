"""The plain reference: what a solve's answer must satisfy, and a plain
D3CA for the precision control.

Imports nothing of the program under test.  A problem is the
benchmark's own data (:mod:`chipbench.problem`): a dense ``X`` or a CSR
triplet, labels ``y`` in {-1, +1}, and ``lam``.  The objective is the
hinge-loss SVM the paper solves, with the ``lam / 2`` convention::

    P(w)     = (1/n) sum_i max(0, 1 - y_i x_i.w) + (lam/2) |w|^2
    D(alpha) = (1/n) sum_i alpha_i y_i - (lam/2) |v(alpha)|^2,
    v(alpha) = X^T alpha / (lam n),   feasible iff alpha_i y_i in [0, 1]

By weak duality ``P(w) - D(alpha)`` bounds how far ``w`` is from the
optimum for ANY feasible ``alpha``: the certificate needs no optimum.
"""
from __future__ import annotations

import numpy as np

#: rows per block of the float64 products, so that a dense problem never
#: needs a float64 copy of the whole matrix
ROW_BLOCK = 1024


def matvec(problem, w):
    """X @ w in float64."""
    w = np.asarray(w, np.float64)
    if problem.dense is not None:
        X = problem.dense
        return np.concatenate([X[i:i + ROW_BLOCK].astype(np.float64) @ w
                               for i in range(0, X.shape[0], ROW_BLOCK)])
    prod = problem.data.astype(np.float64) * w[problem.indices]
    return np.bincount(problem.row_ids, weights=prod,
                       minlength=problem.n)


def rmatvec(problem, a):
    """X^T @ a in float64."""
    a = np.asarray(a, np.float64)
    if problem.dense is not None:
        X = problem.dense
        out = np.zeros((problem.m,), np.float64)
        for i in range(0, X.shape[0], ROW_BLOCK):
            out += a[i:i + ROW_BLOCK] @ X[i:i + ROW_BLOCK].astype(np.float64)
        return out
    prod = problem.data.astype(np.float64) * a[problem.row_ids]
    return np.bincount(problem.indices, weights=prod, minlength=problem.m)


def certify(problem, w, alpha, *, lam: float = None,
            loss: str = "hinge") -> dict:
    """The answer ``(w, alpha)`` judged in float64, at ``lam`` (default:
    the problem's).

    ``alpha`` is projected onto the feasible box first, which keeps
    ``D`` a lower bound of the optimum.  Returns the primal and dual
    objectives, the certified gap ``P(w) - D(alpha)``, and ``map_err``:
    how far ``w`` lies from the primal-dual map of ``alpha``, relative to
    that map (D3CA's step 9 makes ``w`` exactly that map).  Only the
    hinge loss is written here; another is an error.
    """
    if loss != "hinge":
        raise ValueError(f"the reference has no {loss!r} loss")
    y = problem.y.astype(np.float64)
    n = problem.n
    lam = problem.lam if lam is None else float(lam)
    w = np.asarray(w, np.float64)
    a = np.asarray(alpha, np.float64)
    a = np.clip(a * y, 0.0, 1.0) * y
    primal = (np.maximum(0.0, 1.0 - y * matvec(problem, w)).mean()
              + 0.5 * lam * float(w @ w))
    v = rmatvec(problem, a) / (lam * n)
    dual = float(a @ y) / n - 0.5 * lam * float(v @ v)
    map_err = float(np.linalg.norm(w - v) / max(np.linalg.norm(v), 1e-30))
    return {"primal": float(primal), "dual": dual,
            "gap": float(primal - dual), "map_err": map_err}


# ---------------------------------------------------------------------------
# plain D3CA (the control runs it in a lower precision)
# ---------------------------------------------------------------------------

def dense_on_device(problem, dtype, shape):
    """The problem's matrix, zero-padded to ``shape``, dense on the
    default device in ``dtype``, built in one call."""
    import jax
    import jax.numpy as jnp
    n, m = problem.n, problem.m
    if problem.dense is not None:
        return jax.jit(lambda X: jnp.zeros(shape, dtype).at[:n, :m].set(
            X.astype(dtype)))(problem.dense)
    return jax.jit(lambda r, c, v: jnp.zeros(shape, dtype).at[r, c].set(
        v.astype(dtype)))(problem.row_ids, problem.indices, problem.data)


def plain_outer(n: int, m: int, *, lam: float, P: int, Q: int, dtype):
    """The jitted outer iteration of :func:`plain_d3ca`:
    ``outer(alpha (P, n_p), w (Q m_q,), key, X (P n_p, Q m_q), y (P n_p,))
    -> (alpha, w, gap)``, every array in ``dtype``."""
    import jax
    import jax.numpy as jnp
    n_p, m_q = -(-n // P), -(-m // Q)
    lam_n = lam * n

    def epoch(xb, yc, a, wq, key):
        rows = jax.random.randint(key, (n_p,), 0, n_p)

        def step(carry, i):
            da, wl = carry
            xi, yi = xb[i], yc[i]
            ai = a[i] + da[i]
            sq = jnp.maximum(jnp.dot(xi, xi), jnp.asarray(1e-12, dtype))
            d = (yi / Q - jnp.dot(xi, wl)) * jnp.asarray(lam_n, dtype) / sq
            lo = jnp.where(yi > 0, 0.0, -1.0).astype(dtype)
            hi = jnp.where(yi > 0, 1.0, 0.0).astype(dtype)
            d = jnp.where(yi == 0, jnp.zeros_like(d),
                          jnp.clip(ai + d, lo, hi) - ai)
            return (da.at[i].add(d),
                    wl + (d / jnp.asarray(lam_n, dtype)) * xi), None

        (da, _), _ = jax.lax.scan(step, (jnp.zeros_like(a), wq), rows)
        return da

    cells = jax.vmap(jax.vmap(epoch, (1, None, None, 0, None)),
                     (0, 0, 0, None, 0))

    @jax.jit
    def outer(alpha, w, key, X, y):
        blocks = X.reshape(P, n_p, Q, m_q)
        keys = jax.random.split(key, P)
        da = cells(blocks, y.reshape(P, n_p), alpha, w.reshape(Q, m_q), keys)
        alpha = alpha + da.mean(axis=1) / P
        w = (jnp.einsum("pn,pnqm->qm", alpha, blocks).reshape(-1)
             / jnp.asarray(lam_n, dtype))
        z = X @ w
        primal = (jnp.sum(jnp.maximum(0, 1 - y * z)) / n
                  + lam / 2 * jnp.dot(w, w))
        dual = jnp.dot(alpha.reshape(-1), y) / n - lam / 2 * jnp.dot(w, w)
        return alpha, w, primal - dual

    return outer


class PlainD3CA:
    """Algorithm 1 of the paper, written plainly, every array in ``dtype``,
    on one device.

    Per outer iteration each of the P x Q cells runs one epoch of n_p
    randomly drawn dual coordinate steps on its block against its copy of
    ``w``'s block (the conjugate scaled by 1/Q); the dual deltas are
    averaged over q and added with weight 1/P; ``w`` is recomputed from
    the dual.  A solve stops when its own duality gap, computed in
    ``dtype``, falls below the target.
    """

    def __init__(self, problem, *, P: int, Q: int, dtype):
        import jax.numpy as jnp
        self.n, self.m, self.P, self.Q, self.dtype = (
            problem.n, problem.m, P, Q, dtype)
        self.n_p, self.m_q = -(-self.n // P), -(-self.m // Q)
        self.X = dense_on_device(problem, dtype,
                                 (P * self.n_p, Q * self.m_q))
        self.y = jnp.zeros((P * self.n_p,), dtype).at[:self.n].set(
            jnp.asarray(problem.y, dtype))
        self._outer = {}

    def solve(self, *, lam: float, target: float, max_iters: int,
              seed: int):
        """Returns ``(w, alpha, gap, iters, converged)``, the iterates as
        numpy float64."""
        import jax
        import jax.numpy as jnp
        if lam not in self._outer:
            self._outer[lam] = plain_outer(self.n, self.m, lam=lam, P=self.P,
                                           Q=self.Q, dtype=self.dtype)
        outer = self._outer[lam]
        alpha = jnp.zeros((self.P, self.n_p), self.dtype)
        w = jnp.zeros((self.Q * self.m_q,), self.dtype)
        key = jax.random.PRNGKey(seed)
        gap, it, converged = float("inf"), 0, False
        for it in range(1, max_iters + 1):
            alpha, w, g = outer(alpha, w, jax.random.fold_in(key, it),
                                self.X, self.y)
            gap = float(g)
            if gap < target:
                converged = True
                break
        return (np.asarray(w, np.float64)[:self.m],
                np.asarray(alpha, np.float64).reshape(-1)[:self.n], gap, it,
                converged)
