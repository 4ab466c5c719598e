"""Layouts and single-element access shared by the solver kernels.

A TPU block must tile as (8, 128): its last two dimensions are multiples
of 8 and 128, or equal to the whole array's.  The solver kernels visit
one observation per step, so they never fetch a one-row block:

  * a data row is read out of the (8, W) tile that holds it, fetched at
    ``i // 8`` and sliced at ``i % 8`` inside the kernel;
  * per-observation vectors (labels, mask, duals, anchor products) are
    laid out lane-dense as (ceil(n / 128), 128) and stay resident in
    VMEM for the whole epoch.  Element ``i`` is lane ``i % 128`` of row
    ``i // 128``; it is read with a masked lane sum (exact: one term is
    nonzero) and updated with a masked add.  An (n, 1) column would pad
    every row to 128 lanes, 128x the bytes.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 8


def static_scalar(v) -> bool:
    """True when ``v`` can be baked into a kernel as a compile-time
    constant (a plain host scalar, not a traced value)."""
    return isinstance(v, (int, float, np.integer, np.floating))


def row_tile(n: int) -> int:
    """Rows per fetched data tile: 8, or the whole array when shorter."""
    return min(SUBLANES, n)


def to_lanes(v):
    """(n,) -> (ceil(n / 128), 128) float32, zero-padded."""
    v = jnp.asarray(v, jnp.float32)
    pad = -v.shape[0] % LANES
    return jnp.pad(v, (0, pad)).reshape(-1, LANES)


def from_lanes(a, n: int):
    """Inverse of :func:`to_lanes`: the first ``n`` elements."""
    return a.reshape(-1)[:n]


def lane_mask(j):
    """(1, 128) bool, true at lane ``j % 128``."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    return lane == j % LANES


def read_lane(ref, j):
    """Element ``j`` of a lane-dense ref, as a (1, 1) float32 value."""
    row = ref[pl.ds(j // LANES, 1), :].astype(jnp.float32)
    return jnp.sum(jnp.where(lane_mask(j), row, 0.0), axis=1, keepdims=True)


def add_lane(ref, j, d):
    """``ref[j] += d`` on a lane-dense ref; ``d`` is (1, 1) or scalar."""
    rows = pl.ds(j // LANES, 1)
    ref[rows, :] = ref[rows, :] + jnp.where(lane_mask(j), d, 0.0)
