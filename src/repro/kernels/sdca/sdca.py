"""Pallas TPU kernel: local SDCA epoch (Algorithm 2 inner loop).

TPU adaptation of the paper's random-access CPU loop (DESIGN.md §2):

  * the random coordinate order is materialized ONCE per epoch on the host
    and fed through scalar prefetch (``PrefetchScalarGridSpec``) -- the
    DMA of the (8, m_q) tile holding row idx[h+1] is issued while step h
    computes (Pallas double-buffers the tiles); the row itself is sliced
    out of its tile inside the kernel (``repro.kernels.lanes``);
  * the grid is the step counter (TPU grids execute sequentially, which
    is exactly the dependency structure of dual coordinate ascent);
  * labels, mask, alpha0 and the dual deltas are lane-dense (n_p / 128,
    128) blocks resident in VMEM for the whole epoch, and the running
    primal block w lives in its resident output block; nothing but one
    data tile moves per step;
  * the paper's beta step-size variant (step_mode="beta", beta = lam/t)
    rides along as a second scalar-prefetch argument -- beta changes every
    outer iteration, so it must be a runtime input, not a compile-time
    constant.

Supported losses: hinge (closed form), squared.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .. import resolve_interpret
from ..lanes import (add_lane, from_lanes, read_lane, row_tile, static_scalar,
                     to_lanes)


def sdca_delta(loss, a_i, zloc, yi, denom, lam_v, n_v, Q):
    """Closed-form dual step of one coordinate; shared by the dense and
    sparse kernels.  All array arguments are (1, 1) values."""
    denom = jnp.maximum(denom, 1e-12)
    if loss == "hinge":
        d = (yi / Q - zloc) * lam_v * n_v / denom
        lo = jnp.where(yi > 0, 0.0, -1.0)
        hi = jnp.where(yi > 0, 1.0, 0.0)
        return jnp.clip(a_i + d, lo, hi) - a_i
    if loss == "squared":
        num = yi / Q - a_i / (2.0 * Q) - zloc
        den = 1.0 / (2.0 * Q) + denom / (lam_v * n_v)
        return num / jnp.maximum(den, 1e-12)
    raise ValueError(loss)


def _kernel(idx_ref,            # scalar prefetch: (steps,) int32
            params_ref,         # scalar prefetch: (3,) f32 [beta, lam, n]
            x_ref,              # (tr, m_q) tile holding row idx[h]
            y_ref,              # (n_p / 128, 128) labels
            mask_ref,           # (n_p / 128, 128)
            alpha_ref,          # (n_p / 128, 128) alpha0
            w0_ref,             # (1, m_q) initial w block
            dalpha_ref,         # out: (n_p / 128, 128) dual deltas
            w_ref,              # out: (1, m_q) running w
            *, lam, n, Q, tr, loss, use_beta, runtime):
    h = pl.program_id(0)

    @pl.when(h == 0)
    def _init():
        w_ref[...] = w0_ref[...].astype(jnp.float32)
        dalpha_ref[...] = jnp.zeros_like(dalpha_ref)

    i = idx_ref[h]
    xi = x_ref[pl.ds(i % tr, 1), :].astype(jnp.float32)
    yi = read_lane(y_ref, i)
    mi = read_lane(mask_ref, i)
    a_i = read_lane(alpha_ref, i) + read_lane(dalpha_ref, i)
    # runtime mode (the fleet path): lam / n arrive as traced scalars in
    # the prefetch params vector; static mode bakes the Python constants
    lam_v = params_ref[1] if runtime else lam
    n_v = params_ref[2] if runtime else n

    w = w_ref[...]
    zloc = jnp.sum(xi * w, axis=1, keepdims=True)
    denom = (params_ref[0] if use_beta
             else jnp.sum(xi * xi, axis=1, keepdims=True))
    d = sdca_delta(loss, a_i, zloc, yi, denom, lam_v, n_v, Q) * mi

    w_ref[...] = w + (d / (lam_v * n_v)) * xi
    add_lane(dalpha_ref, i, d)


def sdca_epoch_pallas(x, y, mask, alpha0, w0, idx, *, lam, n, Q,
                      loss: str = "hinge", beta=None, interpret=None):
    """Drop-in kernel version of ``ref.sdca_epoch_ref``.

    x: (n_p, m_q) f32; idx: (steps,) int32.  ``beta`` (a runtime scalar,
    may be traced) selects the paper's step_mode="beta" denominator.
    ``lam`` / ``n`` may also be traced (the fleet's per-tenant path);
    they then ride the same scalar-prefetch vector as beta.
    ``interpret=None`` follows ``repro.kernels.default_interpret``.
    Returns (dalpha, w_final).
    """
    n_p, m_q = x.shape
    steps = idx.shape[0]
    tr = row_tile(n_p)
    use_beta = beta is not None
    runtime = not (static_scalar(lam) and static_scalar(n))
    params = jnp.stack([
        jnp.asarray(beta if use_beta else 0.0, jnp.float32),
        jnp.asarray(lam, jnp.float32),
        jnp.asarray(n, jnp.float32)])
    y2, mask2, alpha2 = to_lanes(y), to_lanes(mask), to_lanes(alpha0)
    kern = functools.partial(
        _kernel,
        lam=None if runtime else float(lam),
        n=None if runtime else int(n),
        Q=int(Q), tr=tr, loss=loss, use_beta=use_beta, runtime=runtime)
    whole = lambda h, idx_ref, p: (0, 0)  # noqa: E731
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(steps,),
        in_specs=[
            pl.BlockSpec((tr, m_q), lambda h, idx_ref, p: (idx_ref[h] // tr,
                                                          0)),
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec((1, m_q), whole),
        ],
        out_specs=[
            pl.BlockSpec(y2.shape, whole),
            pl.BlockSpec((1, m_q), whole),
        ],
    )
    dalpha, w_fin = pl.pallas_call(
        kern,
        name="sdca_dense",
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct(y2.shape, jnp.float32),
            jax.ShapeDtypeStruct((1, m_q), jnp.float32),
        ],
        interpret=resolve_interpret(interpret),
    )(idx, params, x, y2, mask2, alpha2, w0[None, :])
    return from_lanes(dalpha, n_p), w_fin[0]
