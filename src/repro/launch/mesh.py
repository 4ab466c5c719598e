"""Mesh builders: every mesh of the program is made here.

Defined as FUNCTIONS so importing this module never touches jax device
state (required by the dry-run's forced host-device count).  Every axis
is ``AxisType.Auto``: jax 0.9 makes Explicit axes by default, under
which the solvers' sharding-agnostic jnp code fails to resolve its out
shardings.
"""
from __future__ import annotations

import functools

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes):
    """A mesh over ``jax.devices()`` with the given shape and axis names,
    every axis Auto."""
    axes = tuple(axes)
    return jax.make_mesh(tuple(shape), axes, (AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 two pods (512 chips)."""
    if multi_pod:
        return make_mesh((2, 16, 16), ("pod", "data", "model"))
    return make_mesh((16, 16), ("data", "model"))


@functools.lru_cache(maxsize=None)
def make_grid_mesh(P: int, Q: int):
    """The paper's P x Q doubly distributed grid.

    Memoized: a Mesh is immutable and building one re-enumerates
    devices, so repeated solves (the online update loop, the fleet)
    reuse the same object -- which also keeps jit caches warm, since
    mesh identity participates in shard_map cache keys.
    """
    return make_mesh((P, Q), ("data", "model"))
