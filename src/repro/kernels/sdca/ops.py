"""Jitted public wrapper for the SDCA epoch kernel."""
from __future__ import annotations

from functools import partial

import jax

from .ref import sdca_epoch_ref
from .sdca import sdca_epoch_pallas


@partial(jax.jit, static_argnames=("lam", "n", "Q", "loss", "backend",
                                   "interpret"))
def sdca_epoch(x, y, mask, alpha0, w0, idx, *, lam, n, Q, loss="hinge",
               backend="pallas", beta=None, interpret=None):
    """One local SDCA epoch on a data block.

    backend="pallas": TPU kernel (``interpret=None`` follows
    ``repro.kernels.default_interpret``).
    backend="ref": pure-jnp oracle.
    ``beta`` (runtime scalar or None) selects step_mode="beta".
    """
    if backend == "ref":
        return sdca_epoch_ref(x, y, mask, alpha0, w0, idx,
                              lam=lam, n=n, Q=Q, loss=loss, beta=beta)
    return sdca_epoch_pallas(x, y, mask, alpha0, w0, idx,
                             lam=lam, n=n, Q=Q, loss=loss, beta=beta,
                             interpret=interpret)
