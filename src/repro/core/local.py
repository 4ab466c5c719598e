"""Cell-local solvers shared by the simulated grid and shard_map executions.

Each function sees exactly the data one worker of the P x Q grid owns:
``x`` of shape (n_p, m_q), labels/mask (n_p,), and the relevant slices of
the primal/dual vectors.  They are pure and jit/vmap/shard_map friendly.

Both take a ``backend`` knob ("ref" | "pallas"):

  * ``backend="ref"`` runs the pure-jnp lax.scan implementation below;
  * ``backend="pallas"`` dispatches to the Pallas TPU kernels in
    ``repro.kernels.sdca`` / ``repro.kernels.svrg`` (compiled on a TPU,
    the Pallas interpreter elsewhere).  The coordinate order is drawn
    from the same PRNG key either way, so the two backends agree to
    float tolerance.  The kernels support hinge and squared losses; logistic
    raises (use backend="ref").

The knob is threaded end-to-end from the solver API
(``repro.core.solver``) through both engines, so the kernels run inside
the vmap grid and inside each shard_map cell alike.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .losses import Loss

PALLAS_LOSSES = ("hinge", "squared")


def _check_pallas_loss(loss: Loss):
    if loss.name not in PALLAS_LOSSES:
        raise NotImplementedError(
            f"local_backend='pallas' supports losses {PALLAS_LOSSES}, not "
            f"{loss.name!r}; use local_backend='ref' for {loss.name}")


# ----------------------------------------------------------------------------
# Local SDCA (Algorithm 2): one epoch of randomized dual coordinate ascent on
# the local block, with the conjugate term scaled by 1/Q.
# ----------------------------------------------------------------------------

def local_sdca(loss: Loss, x, y, mask, alpha0, w0, *, lam, n, Q,
               steps, key, step_mode: str = "exact", beta=None,
               backend: str = "ref"):
    """Run ``steps`` SDCA coordinate updates on the local block.

    Args:
      x: (n_p, m_q) local data block.
      y, mask: (n_p,) labels and row-validity mask.
      alpha0: (n_p,) local view of the shared dual block alpha_[p, .].
      w0: (m_q,) local view of the shared primal block w_[., q].
      lam, n: global regularization and *global* observation count.
      Q: number of feature partitions (scales the conjugate by 1/Q).
      steps: number of coordinate updates (H in Algorithm 2).
      key: PRNG key for the coordinate order (shared across q so every
        feature block visits the same observation sequence, matching the
        paper's per-partition sampling).
      step_mode: "exact" uses ||x_i||^2; "beta" uses the paper's step-size
        parameter ``beta`` (they use beta = lam / t).
      backend: "ref" (pure jnp) | "pallas" (TPU kernel).

    Returns:
      delta_alpha: (n_p,) accumulated dual change of this cell.
    """
    n_p = x.shape[0]
    idx = jax.random.randint(key, (steps,), 0, n_p)
    use_beta = step_mode == "beta"

    if backend == "pallas":
        _check_pallas_loss(loss)
        from repro.kernels.sdca import sdca_epoch_pallas
        dalpha, _ = sdca_epoch_pallas(
            x, y, mask, alpha0, w0, idx, lam=lam, n=n, Q=Q, loss=loss.name,
            beta=(beta if use_beta else None))
        return dalpha
    if backend != "ref":
        raise ValueError(f"unknown local backend {backend!r}")

    x_sq = jnp.sum(x * x, axis=1)  # (n_p,)

    def body(carry, i):
        w, dalpha = carry
        xi = x[i]
        zloc = xi @ w                     # local contribution to x_i . w
        a_i = alpha0[i] + dalpha[i]
        d = loss.sdca_delta(a_i, x_sq[i], zloc, y[i], lam, n, Q,
                            beta=(beta if use_beta else None))
        d = d * mask[i]                   # padded rows never move
        w = w + (d / (lam * n)) * xi
        dalpha = dalpha.at[i].add(d)
        return (w, dalpha), None

    (w_fin, dalpha), _ = jax.lax.scan(body, (w0, jnp.zeros_like(alpha0)), idx)
    del w_fin  # D3CA recomputes w from the primal-dual map (step 9)
    return dalpha


# ----------------------------------------------------------------------------
# Local RADiSA inner loop (Algorithm 3 steps 6-10): L SVRG steps on the
# assigned sub-block of coordinates.
# ----------------------------------------------------------------------------

def local_svrg(loss: Loss, x_sub, y, mask, z_anchor, w_anchor_sub, mu_sub,
               *, lam, L, eta, key, lo=None, backend: str = "ref"):
    """L SVRG steps on one feature sub-block.

    The stochastic partial gradient uses the anchor inner products
    ``z_anchor[j] = x_j^T w_tilde`` (computed once, doubly distributed) and
    corrects locally:  x_j^T w  ~=  z_anchor[j] + x_j[sub]^T (w - w_tilde[sub]).

    Args:
      x_sub: (n_p, m_sub) columns of the assigned sub-block -- OR, when
        ``lo`` is given, the full (n_p, m_q) block from which each sampled
        ROW's ``[lo:lo+m_sub]`` columns are sliced inside the loop.
        Slicing the block before the loop reads pathologically: XLA fuses
        the loop-invariant column slice into the per-step row gather, so
        every inner step re-reads the whole sub-block (104.9 MB/step
        measured; EXPERIMENTS.md §Perf cell 3).  Row-first gather then a
        column slice of ONE row keeps the step at ~KB.
      z_anchor: (n_p,) full inner products at the anchor point w_tilde.
      w_anchor_sub: (m_sub,) anchor coordinates of the sub-block.
      mu_sub: (m_sub,) coordinates of the full anchor gradient of F
        (includes the 2*lam*w_tilde term).
      eta: learning rate eta_t.
      backend: "ref" (pure jnp) | "pallas" (TPU kernel).

    Returns:
      w_sub: (m_sub,) updated sub-block.
    """
    n_p = x_sub.shape[0]
    m_sub = w_anchor_sub.shape[0]
    idx = jax.random.randint(key, (L,), 0, n_p)

    if backend == "pallas":
        _check_pallas_loss(loss)
        from repro.kernels.svrg import svrg_inner_pallas
        if lo is None:
            x_k = x_sub
        else:
            # The kernel DMAs the (8, m_sub) tile holding each sampled
            # row straight out of this slice (scalar prefetch), so the fused
            # column-slice pathology of the jnp path does not apply: the
            # slice is materialized once per outer iteration, not once
            # per inner step.
            x_k = jax.lax.dynamic_slice(x_sub, (0, lo), (n_p, m_sub))
        return svrg_inner_pallas(x_k, y, mask, z_anchor, w_anchor_sub,
                                 mu_sub, idx, lam=lam, eta=eta,
                                 loss=loss.name)
    if backend != "ref":
        raise ValueError(f"unknown local backend {backend!r}")

    def body(w, j):
        if lo is None:
            xj = x_sub[j]
        else:
            xj = jax.lax.dynamic_slice(x_sub[j], (lo,), (m_sub,))
        corr = xj @ (w - w_anchor_sub)
        z = z_anchor[j] + corr
        g_new = loss.grad(z, y[j])
        g_old = loss.grad(z_anchor[j], y[j])
        # SVRG direction on the sub-block; the regularizer is corrected from
        # the anchor to the current point exactly (it is quadratic).
        g = (g_new - g_old) * xj * mask[j] + mu_sub \
            + lam * (w - w_anchor_sub)
        return w - eta * g, None

    w_fin, _ = jax.lax.scan(body, w_anchor_sub, idx)
    return w_fin


# ----------------------------------------------------------------------------
# Sparse-cell variants: the block is a padded-ELL pair (cols, vals) of shape
# (n_p, k) with block-local column ids; k ~ max row nnz, so a cell's memory
# and per-step work scale with the nonzero count instead of m_q.  Padding
# slots carry (col=0, val=0): gathers read w[0] harmlessly and scatters add
# zero, so they are inert.  Same PRNG draw as the dense variants, so sparse
# and dense runs agree to float tolerance on identical data.
# ----------------------------------------------------------------------------

def local_sdca_sparse(loss: Loss, cols, vals, y, mask, alpha0, w0, *, lam, n,
                      Q, steps, key, step_mode: str = "exact", beta=None,
                      backend: str = "ref"):
    """Sparse-cell version of :func:`local_sdca`.

    Args:
      cols, vals: (n_p, k) padded-ELL local block (block-local columns).
      w0: (m_q,) dense local view of the shared primal block.
      Everything else as in :func:`local_sdca`.

    Returns:
      delta_alpha: (n_p,) accumulated dual change of this cell.
    """
    n_p = cols.shape[0]
    idx = jax.random.randint(key, (steps,), 0, n_p)
    use_beta = step_mode == "beta"

    if backend == "pallas":
        _check_pallas_loss(loss)
        from repro.kernels.sdca import sdca_epoch_sparse_pallas
        dalpha, _ = sdca_epoch_sparse_pallas(
            cols, vals, y, mask, alpha0, w0, idx, lam=lam, n=n, Q=Q,
            loss=loss.name, beta=(beta if use_beta else None))
        return dalpha
    if backend != "ref":
        raise ValueError(f"unknown local backend {backend!r}")

    x_sq = jnp.sum(vals * vals, axis=1)  # (n_p,)

    def body(carry, i):
        w, dalpha = carry
        ci, vi = cols[i], vals[i]
        zloc = jnp.sum(vi * w[ci])        # local contribution to x_i . w
        a_i = alpha0[i] + dalpha[i]
        d = loss.sdca_delta(a_i, x_sq[i], zloc, y[i], lam, n, Q,
                            beta=(beta if use_beta else None))
        d = d * mask[i]                   # padded rows never move
        w = w.at[ci].add((d / (lam * n)) * vi)
        dalpha = dalpha.at[i].add(d)
        return (w, dalpha), None

    (w_fin, dalpha), _ = jax.lax.scan(body, (w0, jnp.zeros_like(alpha0)), idx)
    del w_fin  # D3CA recomputes w from the primal-dual map (step 9)
    return dalpha


def local_svrg_sparse(loss: Loss, cols, vals, y, mask, z_anchor,
                      w_anchor_sub, mu_sub, *, lam, L, eta, key, lo=None,
                      backend: str = "ref"):
    """Sparse-cell version of :func:`local_svrg`.

    The cell always receives the FULL feature block as (n_p, k) ELL; the
    assigned sub-block window ``[lo, lo + m_sub)`` (``lo`` may be a
    traced scalar -- it follows the per-iteration permutation) is
    selected by masking the in-window entries of each sampled row.
    ``lo=None`` means the window is the whole block (RADiSA-avg).

    Returns:
      w_sub: (m_sub,) updated sub-block iterate.
    """
    n_p = cols.shape[0]
    m_sub = w_anchor_sub.shape[0]
    idx = jax.random.randint(key, (L,), 0, n_p)
    lo = 0 if lo is None else lo

    if backend == "pallas":
        _check_pallas_loss(loss)
        from repro.kernels.svrg import svrg_inner_sparse_pallas
        return svrg_inner_sparse_pallas(
            cols, vals, y, mask, z_anchor, w_anchor_sub, mu_sub, idx,
            lam=lam, eta=eta, lo=lo, loss=loss.name)
    if backend != "ref":
        raise ValueError(f"unknown local backend {backend!r}")

    def body(w, j):
        ci, vi = cols[j], vals[j]
        rel = ci - lo
        sel = ((rel >= 0) & (rel < m_sub)).astype(vi.dtype)
        relc = jnp.clip(rel, 0, m_sub - 1)
        diff = w - w_anchor_sub
        corr = jnp.sum(vi * sel * diff[relc])   # x_j[window] @ (w - wa)
        z = z_anchor[j] + corr
        gdiff = (loss.grad(z, y[j]) - loss.grad(z_anchor[j], y[j])) * mask[j]
        g_sparse = jnp.zeros((m_sub,), vi.dtype).at[relc].add(
            gdiff * vi * sel)
        g = g_sparse + mu_sub + lam * diff
        return w - eta * g, None

    w_fin, _ = jax.lax.scan(body, w_anchor_sub, idx)
    return w_fin
