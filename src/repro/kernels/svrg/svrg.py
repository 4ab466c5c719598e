"""Pallas TPU kernel: RADiSA inner loop (Algorithm 3 steps 7-10).

Same TPU scheme as the SDCA kernel: sequential step grid, scalar-prefetched
minibatch order driving the DMA of the (8, m_sub) tile that holds each
sampled row, lane-dense per-observation vectors and the anchor quantities
resident in VMEM for all L steps, and the sub-block iterate w kept in its
resident output block.  The step size eta_t = gamma / (1 + sqrt(t-1))
changes every outer iteration, so it is a runtime scalar-prefetch input
rather than a compile-time constant.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ..lanes import read_lane, row_tile, static_scalar, to_lanes


def loss_grad(loss, z, y):
    if loss == "hinge":
        return jnp.where(y * z < 1.0, -y, 0.0)
    if loss == "squared":
        return 2.0 * (z - y)
    raise ValueError(loss)


def _kernel(idx_ref,            # scalar prefetch: (L,) int32
            params_ref,         # scalar prefetch: (2,) f32 [eta, lam]
            x_ref,              # (tr, m_sub) tile holding row idx[h]
            y_ref,              # (n_p / 128, 128)
            mask_ref,           # (n_p / 128, 128)
            z_ref,              # (n_p / 128, 128) anchor inner products
            w_anchor_ref,       # (1, m_sub)
            mu_ref,             # (1, m_sub)
            w_ref,              # out: (1, m_sub) running iterate
            *, lam, tr, loss, runtime):
    h = pl.program_id(0)

    @pl.when(h == 0)
    def _init():
        w_ref[...] = w_anchor_ref[...].astype(jnp.float32)

    j = idx_ref[h]
    xj = x_ref[pl.ds(j % tr, 1), :].astype(jnp.float32)
    yj = read_lane(y_ref, j)
    mj = read_lane(mask_ref, j)
    zj = read_lane(z_ref, j)
    wa = w_anchor_ref[...].astype(jnp.float32)
    mu = mu_ref[...].astype(jnp.float32)
    # runtime mode (fleet): traced lam from the prefetch params;
    # static mode bakes the Python constant (kernel unchanged)
    lam_v = params_ref[1] if runtime else lam

    w = w_ref[...]
    z = zj + jnp.sum(xj * (w - wa), axis=1, keepdims=True)
    g = (loss_grad(loss, z, yj) - loss_grad(loss, zj, yj)) * xj * mj \
        + mu + lam_v * (w - wa)
    w_ref[...] = w - params_ref[0] * g


def svrg_inner_pallas(x_sub, y, mask, z_anchor, w_anchor, mu_sub, idx, *,
                      lam, eta, loss: str = "hinge", interpret=None):
    """Kernel version of ``ref.svrg_inner_ref``: x_sub (n_p, m_sub),
    idx (L,) int32; ``eta`` and ``lam`` may be traced.  ``interpret=None``
    follows ``repro.kernels.default_interpret``.  Returns w (m_sub,)."""
    n_p, m_sub = x_sub.shape
    L = idx.shape[0]
    tr = row_tile(n_p)
    runtime = not static_scalar(lam)
    params = jnp.stack([jnp.asarray(eta, jnp.float32),
                        jnp.asarray(lam, jnp.float32)])
    y2, mask2, z2 = to_lanes(y), to_lanes(mask), to_lanes(z_anchor)
    kern = functools.partial(_kernel, lam=None if runtime else float(lam),
                             tr=tr, loss=loss, runtime=runtime)
    whole = lambda h, idx_ref, p: (0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(L,),
        in_specs=[
            pl.BlockSpec((tr, m_sub), lambda h, idx_ref, p: (idx_ref[h] // tr,
                                                            0)),
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec((1, m_sub), whole),
            pl.BlockSpec((1, m_sub), whole),
        ],
        out_specs=pl.BlockSpec((1, m_sub), whole),
    )
    w = pl.pallas_call(
        kern,
        name="svrg_dense",
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, m_sub), jnp.float32),
        interpret=resolve_interpret(interpret),
    )(idx, params, x_sub, y2, mask2, z2, w_anchor[None, :], mu_sub[None, :])
    return w[0]
