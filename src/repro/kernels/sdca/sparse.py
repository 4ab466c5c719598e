"""Pallas TPU kernel: local SDCA epoch on a padded-ELL sparse block.

Sparse sibling of ``sdca.sdca_epoch_pallas`` for news20-scale blocks.
Same TPU scheme -- sequential step grid, scalar-prefetched coordinate
order driving the DMA, per-observation vectors lane-dense and resident
in VMEM -- but what moves per step is the (8, k) tile of ELL rows
(column ids + values) holding row idx[h], fetched into SMEM, so the
per-step DMA traffic scales with the rows' nonzero count, not the block
width.

The primal block ``w`` is resident in VMEM lane-dense as (m_q / 128,
128).  The k entries of the row are walked with scalar reads from SMEM:
the gather ``z_loc = sum(vals * w[cols])`` reads row ``c // 128`` of w
and keeps lane ``c % 128``, and the scatter-ADD ``w[cols] += d * vals``
adds at that lane, one entry after another, so duplicate columns
accumulate exactly as a sequential scatter does.  ELL padding slots
carry (col=0, val=0): the gather adds 0 * w[0] and the scatter adds
zero, so they are inert by construction.

Supported losses: hinge (closed form), squared.
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
from .sdca import sdca_delta


def _kernel(idx_ref,            # scalar prefetch: (steps,) int32
            params_ref,         # scalar prefetch: (3,) f32 [beta, lam, n]
            cols_ref,           # SMEM (tr, k) ELL column ids, row idx[h]'s tile
            vals_ref,           # SMEM (tr, k) ELL values
            y_ref,              # (n_p / 128, 128) labels
            mask_ref,           # (n_p / 128, 128)
            alpha_ref,          # (n_p / 128, 128) alpha0
            xsq_ref,            # (n_p / 128, 128) squared row norms
            w0_ref,             # (m_q / 128, 128) initial w block
            dalpha_ref,         # out: (n_p / 128, 128) dual deltas
            w_ref,              # out: (m_q / 128, 128) running w
            *, lam, n, Q, k, tr, loss, use_beta, runtime):
    h = pl.program_id(0)

    @pl.when(h == 0)
    def _init():
        w_ref[...] = w0_ref[...]
        dalpha_ref[...] = jnp.zeros_like(dalpha_ref)

    i = idx_ref[h]
    r = i % tr
    yi = read_lane(y_ref, i)
    mi = read_lane(mask_ref, i)
    a_i = read_lane(alpha_ref, i) + read_lane(dalpha_ref, i)
    # runtime mode (fleet): traced lam / n from the prefetch params;
    # static mode bakes the Python constants (kernel unchanged)
    lam_v = params_ref[1] if runtime else lam
    n_v = params_ref[2] if runtime else n

    def gather(j, acc):
        c = cols_ref[r, j]
        row = w_ref[pl.ds(c // LANES, 1), :]
        return acc + jnp.where(lane_mask(c), vals_ref[r, j] * row, 0.0)

    acc = jax.lax.fori_loop(0, k, gather, jnp.zeros((1, LANES), jnp.float32))
    zloc = jnp.sum(acc, axis=1, keepdims=True)
    denom = params_ref[0] if use_beta else read_lane(xsq_ref, i)
    d = sdca_delta(loss, a_i, zloc, yi, denom, lam_v, n_v, Q) * mi
    coef = d / (lam_v * n_v)

    def scatter(j, carry):
        add_lane(w_ref, cols_ref[r, j], coef * vals_ref[r, j])
        return carry

    jax.lax.fori_loop(0, k, scatter, 0)
    add_lane(dalpha_ref, i, d)


def sdca_epoch_sparse_pallas(cols, vals, y, mask, alpha0, w0, idx, *, lam, n,
                             Q, loss: str = "hinge", beta=None,
                             interpret=None):
    """Sparse-cell kernel version of one local SDCA epoch.

    cols/vals: (n_p, k) padded-ELL block; w0: (m_q,) dense primal block;
    idx: (steps,) int32.  ``beta`` (a runtime scalar, may be traced)
    selects the paper's step_mode="beta" denominator; ``lam`` / ``n``
    may also be traced (the fleet's per-tenant path).
    ``interpret=None`` follows ``repro.kernels.default_interpret``.
    Returns (dalpha, w_final).
    """
    n_p, k = cols.shape
    m_q = w0.shape[0]
    steps = idx.shape[0]
    tr = row_tile(n_p)
    use_beta = beta is not None
    runtime = not (static_scalar(lam) and static_scalar(n))
    params = jnp.stack([
        jnp.asarray(beta if use_beta else 0.0, jnp.float32),
        jnp.asarray(lam, jnp.float32),
        jnp.asarray(n, jnp.float32)])
    vals = vals.astype(jnp.float32)
    y2, mask2, alpha2 = to_lanes(y), to_lanes(mask), to_lanes(alpha0)
    xsq2 = to_lanes(jnp.sum(vals * vals, axis=1))
    w2 = to_lanes(w0)
    kern = functools.partial(
        _kernel,
        lam=None if runtime else float(lam),
        n=None if runtime else int(n),
        Q=int(Q), k=k, tr=tr, loss=loss, use_beta=use_beta,
        runtime=runtime)
    whole = lambda h, idx_ref, p: (0, 0)  # noqa: E731
    ell = pl.BlockSpec((tr, k), lambda h, idx_ref, p: (idx_ref[h] // tr, 0),
                       memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[ell, ell] + [pl.BlockSpec(y2.shape, whole)] * 4
        + [pl.BlockSpec(w2.shape, whole)],
        out_specs=[
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec(w2.shape, whole),
        ],
    )
    dalpha, w_fin = pl.pallas_call(
        kern,
        name="sdca_sparse",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(y2.shape, jnp.float32),
            jax.ShapeDtypeStruct(w2.shape, jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(idx, params, cols.astype(jnp.int32), vals, y2, mask2, alpha2, xsq2, w2)
    return from_lanes(dalpha, n_p), from_lanes(w_fin, m_q)
