"""Small shared helpers for the engines and the solver driver."""
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


def host_nbytes(*operands) -> int:
    """Bytes of the host-resident operands among ``operands``: what
    handing them to a JAX computation sends to the device.  Device
    arrays and None send nothing; an operand with an ``h2d_bytes()``
    method (:class:`~repro.data.sparse.CSRMatrix`) says what it sends."""
    total = 0
    for x in operands:
        if x is None or isinstance(x, jax.Array):
            continue
        if hasattr(x, "h2d_bytes"):
            total += x.h2d_bytes()
        else:
            total += int(getattr(x, "nbytes", 0))
    return total
