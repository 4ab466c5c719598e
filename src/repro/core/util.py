"""Small shared helpers for the shard_map engines."""
from __future__ import annotations

import jax


def pvary(x, axes):
    """Mark ``x`` as varying over the given manual mesh axes.

    shard_map tracks which mesh axes each value varies over; inputs
    that are replicated along an axis must be explicitly promoted before
    being mixed with values that vary along it inside lax control flow.
    """
    axes = tuple(axes)
    if not axes:
        return x
    return jax.lax.pcast(x, axes, to="varying")


def shard_map(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with vma checking off."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


def as_axes(axis) -> tuple:
    """Normalize an axis-name-or-tuple to a tuple of axis names."""
    return (axis,) if isinstance(axis, str) else tuple(axis)


def axes_size(mesh, axis) -> int:
    """Product of mesh sizes over one axis name or a tuple of names."""
    s = 1
    for a in as_axes(axis):
        s *= mesh.shape[a]
    return s


def axes_index(axis):
    """Collapsed linear index over one or several manual mesh axes
    (row-major in the given order), usable inside shard_map."""
    axes = as_axes(axis)
    idx = jax.lax.axis_index(axes[0])
    for a in axes[1:]:
        idx = idx * jax.lax.psum(1, a) + jax.lax.axis_index(a)
    return idx
