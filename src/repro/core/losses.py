"""Convex losses for the ERM objective (1) of Nathan & Klabjan (2016).

    min_w  F(w) = (1/n) sum_i f_i(w^T x_i) + lambda ||w||^2

Every loss provides:
  * ``value(z, y)``      -- f_i(z) parametrized by the label y
  * ``grad(z, y)``       -- df/dz (a subgradient for hinge)
  * ``conj(a, y)``       -- the convex conjugate phi_i*(-a) used by the dual
                            objective (2); +inf outside the dual feasible box
                            is encoded by ``dual_bounds``.
  * ``dual_bounds(y)``   -- feasible interval for the dual variable alpha_i
  * ``sdca_delta(...)``  -- the (approximate) maximizer of the *local* D3CA
                            objective of Algorithm 2 step 3 (scaled by 1/Q):
        max_d  (1/Q) * (-phi*(-(alpha+d))) - (lam*n/2) ||w + d*x/(lam n)||^2
    closed form for hinge / squared, a few Newton steps for logistic.

All functions are elementwise and jit/vmap-safe.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Loss:
    name: str
    value: Callable
    grad: Callable
    conj: Callable
    dual_bounds: Callable
    sdca_delta: Callable

    def objective(self, X, y, w, lam, mask=None, n=None):
        """Primal objective F(w); `mask` marks real (non-padded) rows."""
        n_eff = n if n is not None else (mask.sum() if mask is not None else X.shape[0])
        return self.objective_of_margins(X @ w, y, w, lam, mask, n_eff)

    def objective_of_margins(self, z, y, w, lam, mask, n):
        """F(w) from the margins ``z = X w``: the mean of ``value`` over
        the ``n`` real rows (``mask`` zeroes the padded ones) plus the
        regularizer.  Elementwise in ``z``, ``y``, ``mask``: any shape."""
        vals = self.value(z, y)
        if mask is not None:
            vals = vals * mask
        # NOTE: the paper writes lam*||w||^2 in eq. (1) but its dual (2),
        # primal-dual map (3) and the SDCA closed form are all derived under
        # the standard (lam/2)*||w||^2 convention -- we use the latter
        # consistently (recorded in DESIGN.md §4).
        return vals.sum() / n + 0.5 * lam * jnp.sum(w * w)

    def dual_objective(self, X, y, alpha, lam, mask=None, n=None):
        """Dual objective D(alpha) of eq. (2)."""
        if mask is not None:
            alpha = alpha * mask
        n_eff = n if n is not None else (mask.sum() if mask is not None else X.shape[0])
        v = X.T @ alpha / (lam * n_eff)
        return self.dual_objective_of_map(v, y, alpha, lam, mask, n_eff)

    def dual_objective_of_map(self, v, y, alpha, lam, mask, n):
        """D(alpha) from the primal-dual map ``v = X^T alpha / (lam n)``
        of the masked ``alpha``.  Elementwise in ``y``, ``alpha``,
        ``mask``: any shape."""
        conj_term = self.conj(alpha, y)
        if mask is not None:
            conj_term = conj_term * mask
        return -conj_term.sum() / n - lam / 2.0 * jnp.sum(v * v)


# ----------------------------------------------------------------------------
# hinge: f(z) = max(0, 1 - y z);  phi*(-a) = -a y, feasible iff a*y in [0, 1]
# ----------------------------------------------------------------------------

def _hinge_value(z, y):
    return jnp.maximum(0.0, 1.0 - y * z)


def _hinge_grad(z, y):
    return jnp.where(y * z < 1.0, -y, 0.0)


def _hinge_conj(a, y):
    # phi*(-a) = -a*y  on the feasible box (0 <= a*y <= 1)
    return -a * y


def _hinge_bounds(y):
    lo = jnp.where(y > 0, 0.0, -1.0)
    hi = jnp.where(y > 0, 1.0, 0.0)
    return lo, hi


def _hinge_sdca_delta(alpha, x_sq, zloc, y, lam, n, Q, beta=None):
    """Closed-form local maximizer for hinge (see DESIGN.md §4).

    d/dD [ (1/Q)(alpha+D) y - zloc*D - D^2 ||x||^2/(2 lam n) ] = 0
      =>  D = (y/Q - zloc) * lam*n / ||x||^2,  then clip so that
          (alpha + D) * y in [0, 1].
    ``beta`` (paper's step-size variant) replaces ||x||^2 when given.
    """
    denom = x_sq if beta is None else beta
    denom = jnp.maximum(denom, 1e-12)
    d = (y / Q - zloc) * lam * n / denom
    lo, hi = _hinge_bounds(y)
    return jnp.clip(alpha + d, lo, hi) - alpha


# ----------------------------------------------------------------------------
# squared: f(z) = (z - y)^2 ; phi*(-a) = -a y + a^2 / 4  (unconstrained)
# ----------------------------------------------------------------------------

def _sq_value(z, y):
    return (z - y) ** 2


def _sq_grad(z, y):
    return 2.0 * (z - y)


def _sq_conj(a, y):
    return -a * y + a * a / 4.0


def _sq_bounds(y):
    big = jnp.full_like(y, jnp.inf)
    return -big, big


def _sq_sdca_delta(alpha, x_sq, zloc, y, lam, n, Q, beta=None):
    # d/dD [ (1/Q)((alpha+D) y - (alpha+D)^2/4) - zloc*D - D^2 ||x||^2/(2 lam n) ]
    #  = y/Q - (alpha+D)/(2Q) - zloc - D ||x||^2/(lam n) = 0
    denom_x = x_sq if beta is None else beta
    num = y / Q - alpha / (2.0 * Q) - zloc
    den = 1.0 / (2.0 * Q) + denom_x / (lam * n)
    return num / jnp.maximum(den, 1e-12)


# ----------------------------------------------------------------------------
# logistic: f(z) = log(1 + exp(-y z))
# phi*(-a): with t = a*y in (0,1):  t log t + (1-t) log(1-t)
# ----------------------------------------------------------------------------

def _log_value(z, y):
    return jnp.logaddexp(0.0, -y * z)


def _log_grad(z, y):
    return -y * jax.nn.sigmoid(-y * z)


def _xlogx(t):
    return jnp.where(t > 0, t * jnp.log(jnp.maximum(t, 1e-30)), 0.0)


def _log_conj(a, y):
    t = jnp.clip(a * y, 0.0, 1.0)
    return _xlogx(t) + _xlogx(1.0 - t)


def _log_bounds(y):
    lo = jnp.where(y > 0, 0.0, -1.0)
    hi = jnp.where(y > 0, 1.0, 0.0)
    return lo, hi


def _log_sdca_delta(alpha, x_sq, zloc, y, lam, n, Q, beta=None, newton_iters=8):
    """Newton on g(D) = (1/Q)(-phi*'(-(a+D))) - zloc - D q  with
    q = ||x||^2/(lam n).  Parametrize t = (alpha+D) y in (0,1):
      -d/dD phi*(-(alpha+D)) = y * ( -log(t/(1-t)) )' ... worked out below.
    phi*(-(a)) = t log t + (1-t)log(1-t), t = a y  =>
      d/da phi*(-(a)) = y (log t - log(1-t))
    local obj'(D) = -(1/Q) y log(t/(1-t)) - zloc - D q = 0, t=(a+D)y
    """
    denom_x = x_sq if beta is None else beta
    q = jnp.maximum(denom_x, 1e-12) / (lam * n)
    eps = 1e-6
    # padded rows carry y = 0; dividing by y would poison the masked-out
    # delta with NaN (0 * inf), so divide by a harmless stand-in there
    safe_y = jnp.where(y == 0, 1.0, y)

    def body(D, _):
        t = jnp.clip((alpha + D) * y, eps, 1.0 - eps)
        g = -(1.0 / Q) * y * (jnp.log(t) - jnp.log1p(-t)) - zloc - D * q
        # g'(D) = -(1/Q) * y^2 * (1/t + 1/(1-t)) - q   (y^2 == 1)
        gp = -(1.0 / Q) * (1.0 / t + 1.0 / (1.0 - t)) - q
        D_new = D - g / gp
        # project back so that (alpha + D) y stays inside (0, 1)
        t_new = jnp.clip((alpha + D_new) * y, eps, 1.0 - eps)
        D_new = t_new / safe_y - alpha
        return D_new, None

    D0 = jnp.zeros_like(alpha)
    # start strictly inside the box
    t0 = jnp.clip((alpha + D0) * y, eps, 1.0 - eps)
    D0 = t0 / safe_y - alpha
    D, _ = jax.lax.scan(body, D0, None, length=newton_iters)
    return D


hinge = Loss("hinge", _hinge_value, _hinge_grad, _hinge_conj, _hinge_bounds,
             _hinge_sdca_delta)
squared = Loss("squared", _sq_value, _sq_grad, _sq_conj, _sq_bounds,
               _sq_sdca_delta)
logistic = Loss("logistic", _log_value, _log_grad, _log_conj, _log_bounds,
                _log_sdca_delta)

LOSSES = {fn.name: fn for fn in (hinge, squared, logistic)}


def get_loss(name: str) -> Loss:
    return LOSSES[name]
