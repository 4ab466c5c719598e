"""Pallas TPU kernel: RADiSA inner loop on a padded-ELL sparse block.

Sparse sibling of ``svrg.svrg_inner_pallas``.  The (8, k) tile of ELL
rows of the FULL feature block that holds the sampled row is fetched
into SMEM; the assigned sub-block window ``[lo, lo + m_sub)`` is
selected inside the kernel by zeroing the entries whose block-local
column falls outside it.  ``lo`` changes with the per-iteration
sub-block permutation, so it is a runtime scalar-prefetch input
(alongside the minibatch order and eta_t).

The SVRG direction has a dense part (mu + lam * (w - w_anchor), over
VMEM-resident lane-dense (m_sub / 128, 128) blocks) and a sparse part --
the loss-gradient difference times the row -- scattered entry by entry
into a zeroed VMEM buffer, as in the sparse SDCA kernel.  ELL padding
(col=0, val=0) adds nothing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ..lanes import (LANES, add_lane, from_lanes, lane_mask, read_lane,
                     row_tile, static_scalar, to_lanes)
from .svrg import loss_grad


def _kernel(idx_ref,            # scalar prefetch: (L,) int32
            lo_ref,             # scalar prefetch: (1,) int32 window start
            params_ref,         # scalar prefetch: (2,) f32 [eta, lam]
            cols_ref,           # SMEM (tr, k) ELL column ids, row idx[h]'s tile
            vals_ref,           # SMEM (tr, k) ELL values
            y_ref,              # (n_p / 128, 128)
            mask_ref,           # (n_p / 128, 128)
            z_ref,              # (n_p / 128, 128) anchor inner products
            w_anchor_ref,       # (m_sub / 128, 128)
            mu_ref,             # (m_sub / 128, 128)
            w_ref,              # out: (m_sub / 128, 128) running iterate
            g_vmem,             # scratch: (m_sub / 128, 128) sparse gradient
            *, lam, k, tr, m_sub, loss, runtime):
    h = pl.program_id(0)

    @pl.when(h == 0)
    def _init():
        w_ref[...] = w_anchor_ref[...]

    j = idx_ref[h]
    r = j % tr
    lo = lo_ref[0]
    yj = read_lane(y_ref, j)
    mj = read_lane(mask_ref, j)
    zj = read_lane(z_ref, j)
    # runtime mode (fleet): traced lam from the prefetch params;
    # static mode bakes the Python constant (kernel unchanged)
    lam_v = params_ref[1] if runtime else lam

    def entry(e):
        """Window-relative column (clipped) and in-window value."""
        rel = cols_ref[r, e] - lo
        inside = (rel >= 0) & (rel < m_sub)
        return (jnp.clip(rel, 0, m_sub - 1),
                jnp.where(inside, vals_ref[r, e], 0.0))

    def gather(e, acc):
        c, v = entry(e)
        rows = pl.ds(c // LANES, 1)
        diff = w_ref[rows, :] - w_anchor_ref[rows, :]
        return acc + jnp.where(lane_mask(c), v * diff, 0.0)

    acc = jax.lax.fori_loop(0, k, gather, jnp.zeros((1, LANES), jnp.float32))
    z = zj + jnp.sum(acc, axis=1, keepdims=True)
    gscale = (loss_grad(loss, z, yj) - loss_grad(loss, zj, yj)) * mj

    g_vmem[...] = jnp.zeros_like(g_vmem)

    def scatter(e, carry):
        c, v = entry(e)
        add_lane(g_vmem, c, gscale * v)
        return carry

    jax.lax.fori_loop(0, k, scatter, 0)
    w = w_ref[...]
    wa = w_anchor_ref[...]
    w_ref[...] = w - params_ref[0] * (g_vmem[...] + mu_ref[...]
                                      + lam_v * (w - wa))


def svrg_inner_sparse_pallas(cols, vals, y, mask, z_anchor, w_anchor, mu_sub,
                             idx, *, lam, eta, lo=0, loss: str = "hinge",
                             interpret=None):
    """Sparse-cell kernel version of the RADiSA inner loop.

    cols/vals: (n_p, k) padded-ELL FULL feature block (block-local column
    ids); w_anchor/mu_sub: (m_sub,) sub-block windows; ``lo`` (runtime
    scalar, may be traced) is the window start within the block.
    ``interpret=None`` follows ``repro.kernels.default_interpret``.
    Returns the updated (m_sub,) sub-block iterate.
    """
    n_p, k = cols.shape
    m_sub = w_anchor.shape[0]
    L = idx.shape[0]
    tr = row_tile(n_p)
    lo_arr = jnp.reshape(jnp.asarray(lo, jnp.int32), (1,))
    runtime = not static_scalar(lam)
    params = jnp.stack([jnp.asarray(eta, jnp.float32),
                        jnp.asarray(lam, jnp.float32)])
    y2, mask2, z2 = to_lanes(y), to_lanes(mask), to_lanes(z_anchor)
    wa2, mu2 = to_lanes(w_anchor), to_lanes(mu_sub)
    kern = functools.partial(_kernel, lam=None if runtime else float(lam),
                             k=k, tr=tr, m_sub=m_sub, loss=loss,
                             runtime=runtime)
    whole = lambda h, idx_ref, lo_, p: (0, 0)  # noqa: E731
    ell = pl.BlockSpec((tr, k),
                       lambda h, idx_ref, lo_, p: (idx_ref[h] // tr, 0),
                       memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(L,),
        in_specs=[ell, ell] + [pl.BlockSpec(y2.shape, whole)] * 3
        + [pl.BlockSpec(wa2.shape, whole)] * 2,
        out_specs=pl.BlockSpec(wa2.shape, whole),
        scratch_shapes=[pltpu.VMEM(wa2.shape, jnp.float32)],
    )
    w = pl.pallas_call(
        kern,
        name="svrg_sparse",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(wa2.shape, jnp.float32),
        interpret=resolve_interpret(interpret),
    )(idx, lo_arr, params, cols.astype(jnp.int32), vals.astype(jnp.float32),
      y2, mask2, z2, wa2, mu2)
    return from_lanes(w, m_sub)
