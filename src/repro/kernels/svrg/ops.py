"""Jitted public wrapper for the SVRG inner-loop kernel."""
from __future__ import annotations

from functools import partial

import jax

from .ref import svrg_inner_ref
from .svrg import svrg_inner_pallas


@partial(jax.jit, static_argnames=("lam", "loss", "backend", "interpret"))
def svrg_inner(x_sub, y, mask, z_anchor, w_anchor, mu_sub, idx, *,
               lam, eta, loss="hinge", backend="pallas", interpret=None):
    """RADiSA inner loop; ``eta`` is a runtime scalar (it varies per outer
    iteration), not a compile-time constant."""
    if backend == "ref":
        return svrg_inner_ref(x_sub, y, mask, z_anchor, w_anchor, mu_sub,
                              idx, lam=lam, eta=eta, loss=loss)
    return svrg_inner_pallas(x_sub, y, mask, z_anchor, w_anchor, mu_sub,
                             idx, lam=lam, eta=eta, loss=loss,
                             interpret=interpret)
