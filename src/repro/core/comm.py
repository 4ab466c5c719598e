"""CommSchedule: communication policy as a first-class solver axis.

The paper's three doubly distributed optimizers are all "local
sub-problem solves stitched together by cross-node reductions".  Until
Engine API v2 each engine hard-coded *when* and *how* those reductions
happened (inline ``jax.lax.psum`` calls in the shard_map cells, einsum
contractions in the simulated grid), so a new communication policy --
e.g. the Hogwild-style delayed psum of Fang & Klabjan (2018) -- meant
forking every solver.

This module makes the reduction points explicit:

  * a solver's program builder *declares* its collectives once::

        sched = (CommSchedule()
                 .pmean("dalpha", axis="model")   # step 6 dual average
                 .psum("w_contrib", axis="data")) # step 9 primal-dual map

  * its per-cell step math *executes* them by name through a
    :class:`Comm` handed in by the engine::

        a_new = a_b + comm("dalpha", dalpha) / Pn
        w_new = comm("w_contrib", contrib) / (lam * n)

  * the engine picks the executor -- :class:`SyncComm` applies every
    reduction immediately (today's behavior; works identically inside a
    named-``vmap`` grid and inside a ``shard_map`` cell, because both
    execute ``lax.psum`` over named axes), while :class:`StaleComm`
    applies reductions with bounded staleness tau: the value *returned*
    at outer step t is the reduction *computed* at step
    ``max(1, t - tau)``, carried in a fixed-size FIFO buffer that is
    part of the engine state pytree.  ``tau = 0`` short-circuits to the
    sync path, so the async engine at zero staleness reproduces the
    sync engine exactly (same computation, bit-identical iterates).

Axes are *logical* ("data" = observation partitions, "model" = feature
partitions); the engine maps them to concrete vmap axis names or mesh
axis names (possibly tuples, e.g. ("pod", "data") on a multi-pod mesh).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .util import axes_index

LOGICAL_AXES = ("data", "model")
OPS = ("psum", "pmean", "allgather")


@dataclasses.dataclass(frozen=True)
class Collective:
    """One declared reduction point of a solver program."""

    name: str
    op: str        # "psum" | "pmean" | "allgather"
    axis: str      # logical grid axis reduced over: "data" | "model"

    def __post_init__(self):
        if self.op not in OPS:
            raise ValueError(f"collective {self.name!r}: op={self.op!r}; "
                             f"expected one of {OPS}")
        if self.axis not in LOGICAL_AXES:
            raise ValueError(f"collective {self.name!r}: axis={self.axis!r}; "
                             f"expected one of {LOGICAL_AXES}")

    @property
    def result_axis(self) -> str:
        """Logical axis the reduction *result* still varies over."""
        return "model" if self.axis == "data" else "data"


class CommSchedule:
    """Ordered declaration of a solver's named reduction points."""

    def __init__(self):
        self._points: Dict[str, Collective] = {}

    # -- declaration (chainable) --------------------------------------------
    def _add(self, name: str, op: str, axis: str) -> "CommSchedule":
        if name in self._points:
            raise ValueError(f"collective {name!r} declared twice")
        self._points[name] = Collective(name, op, axis)
        return self

    def psum(self, name: str, *, axis: str) -> "CommSchedule":
        """Declare a sum-reduction over a logical grid axis."""
        return self._add(name, "psum", axis)

    def pmean(self, name: str, *, axis: str) -> "CommSchedule":
        """Declare a mean-reduction over a logical grid axis."""
        return self._add(name, "pmean", axis)

    def allgather(self, name: str, *, axis: str) -> "CommSchedule":
        """Declare a gather over a logical grid axis: the per-cell value
        is stacked along a new leading axis of that axis's extent."""
        return self._add(name, "allgather", axis)

    # -- lookup --------------------------------------------------------------
    def __getitem__(self, name: str) -> Collective:
        try:
            return self._points[name]
        except KeyError:
            raise KeyError(
                f"reduction {name!r} is not declared in this CommSchedule "
                f"(declared: {sorted(self._points)}); declare it with "
                ".psum(name, axis=...) / .pmean(name, axis=...) in the "
                "program builder") from None

    def __contains__(self, name: str) -> bool:
        return name in self._points

    def __iter__(self):
        return iter(self._points.values())

    @property
    def names(self) -> Tuple[str, ...]:
        """The declared collective names, in declaration order."""
        return tuple(self._points)


class Comm:
    """Executor handed to a cell: runs the declared collectives.

    ``axis_map`` maps logical axes to the concrete axis names of the
    execution context (vmap axis names for the simulated grid, mesh axis
    names -- possibly tuples -- for the shard_map engines); ``sizes``
    gives the logical grid extents (P, Q) as static ints.
    """

    def __init__(self, schedule: CommSchedule, axis_map: Dict[str, tuple],
                 sizes: Dict[str, int]):
        self.schedule = schedule
        self.axis_map = {k: (v,) if isinstance(v, str) else tuple(v)
                         for k, v in axis_map.items()}
        self.sizes = dict(sizes)
        self._executed: set = set()
        #: staleness FIFO slots produced this step (only StaleComm fills it)
        self.bufs_out: Dict[str, jnp.ndarray] = {}
        #: exact payload bytes this cell put on the wire, per collective
        #: (executors that shrink the payload -- CompressedComm --
        #: record their own number; everyone else reports the
        #: uncompressed size)
        self.wire_bytes: Dict[str, int] = {}

    # -- cell-facing API -----------------------------------------------------
    def __call__(self, name: str, value):
        """Execute the declared collective ``name`` on ``value``.

        Args:
          name: a collective declared in this executor's CommSchedule.
          value: the cell's per-step payload (any array).

        Returns:
          The reduction result under this executor's policy --
          psum/pmean keep the payload shape, allgather prepends the
          axis extent; staleness executors may return a prior step's
          reduction.

        Raises:
          KeyError: when ``name`` was never declared in the schedule.
          ValueError: when the cell executes the same point twice in
            one outer step.
        """
        point = self.schedule[name]
        if name in self._executed:
            raise ValueError(f"reduction {name!r} executed twice in one "
                             "step; declare a second point instead")
        self._executed.add(name)
        # names the collective's ops in their op_name metadata, and
        # changes no op or number
        with jax.named_scope(f"repro.comm.{name}"):
            out = self._exec(point, value)
        if name not in self.wire_bytes:
            v = jnp.asarray(value)
            self.wire_bytes[name] = (math.prod(v.shape)
                                     * jnp.dtype(v.dtype).itemsize)
        return out

    def axis_index(self, axis: str):
        """Collapsed linear cell index along a logical axis."""
        return axes_index(self.axis_map[axis])

    def axis_size(self, axis: str) -> int:
        """Static extent of a logical grid axis (P or Q)."""
        return self.sizes[axis]

    def finalize(self):
        """Check the schedule contract: every declared point ran once."""
        missing = set(self.schedule.names) - self._executed
        if missing:
            raise ValueError(
                f"declared reductions never executed: {sorted(missing)}; "
                "the cell must run every point of its CommSchedule exactly "
                "once per outer step")

    # -- engine-facing -------------------------------------------------------
    def _exec(self, point: Collective, value):
        raise NotImplementedError


class SyncComm(Comm):
    """Apply every reduction immediately (the paper's synchronous outer
    loop).  Works unchanged inside a named-``vmap`` grid and inside a
    ``shard_map`` cell -- both execute collectives over named axes.

    All reduction executors (this one, :class:`StaleComm`,
    :class:`OverlapComm`) funnel the *actual wire operation* through the
    :meth:`_reduce` hook, so the hierarchical two-level reduction below
    composes with every consumption policy.

    **Hierarchical topology-aware reduction** (``set_topology``): when a
    :class:`~repro.core.comm_model.Topology` with ``pods > 1`` is set,
    a psum/pmean over the pod-split logical axis is executed as a
    two-level axis split -- a full-precision psum over the intra-pod
    axes followed by a codec-compressed psum over the pod axis (the
    cheap fat link carries full floats, the expensive thin link carries
    the codec payload).  The engine expresses the pod split as real
    named axes: the logical axis must map to >= 2 concrete axes with the
    pod axis leading (e.g. ``("pod", "data")`` on a multi-pod mesh, or a
    third named-vmap level on the simulated grid).  A stateful cross-pod
    codec carries its error-feedback residual per (cell, collective) in
    ``hier_ef_in``/``hier_ef_out`` -- threaded through the engine state
    exactly like :class:`CompressedComm`'s residuals, and *distinct*
    from them (a per-collective policy codec compresses the cell
    payload before any reduction; the topology codec compresses the
    intra-pod partial sum).
    """

    #: two-level reduction disabled until ``set_topology`` is called
    topology = None

    def set_topology(self, topology, codec, ef: Optional[dict] = None):
        """Enable hierarchical reduction over ``topology.axis``.

        ``codec`` is the cross-pod codec instance; ``ef`` maps
        collective name -> this cell's error-feedback residual (required
        for stateful codecs, allocated by the engine against the
        intra-pod partial-sum aval == the per-cell payload aval)."""
        self.topology = topology
        self._hier_codec = codec
        self.hier_ef_in = dict(ef or {})
        #: updated residuals, harvested by the engine after the cell runs
        self.hier_ef_out: Dict[str, jnp.ndarray] = {}

    def _reduce(self, point: Collective, value):
        """The wire operation: fresh reduction of this step's value."""
        axes = self.axis_map[point.axis]
        topo = self.topology
        if (topo is not None and topo.pods > 1 and point.axis == topo.axis
                and point.op != "allgather"):
            return self._reduce_hierarchical(point, value, axes)
        if point.op == "psum":
            return jax.lax.psum(value, axes)
        if point.op == "pmean":
            return jax.lax.pmean(value, axes)
        return jax.lax.all_gather(value, axes)

    def _reduce_hierarchical(self, point: Collective, value, axes):
        if len(axes) < 2:
            raise ValueError(
                f"hierarchical reduction over {point.axis!r} needs a "
                f"two-level axis split (pod axis + intra-pod axes); the "
                f"engine mapped it to {axes!r}. Build the program with a "
                "pod-split mesh/grid (topology=...) end to end.")
        pod_axes, inner_axes = axes[:1], axes[1:]
        part = jnp.asarray(jax.lax.psum(value, inner_axes))
        codec = self._hier_codec
        if codec.stateful:
            try:
                err = self.hier_ef_in[point.name]
            except KeyError:
                raise KeyError(
                    f"no cross-pod error-feedback residual for reduction "
                    f"{point.name!r}; the engine allocates one per "
                    "pod-split collective at build time") from None
            deq, new_err = codec.apply(part, err)
            self.hier_ef_out[point.name] = new_err
        else:
            deq, _ = codec.apply(part)
        out = jax.lax.psum(jnp.asarray(deq).astype(part.dtype), pod_axes)
        if point.op == "pmean":
            out = out / self.sizes[point.axis]
        return out

    def _exec(self, point: Collective, value):
        return self._reduce(point, value)


class LocalComm(Comm):
    """Collective-free executor: the program with its exchanges left out.

    Every declared point is executed CELL-LOCALLY: psum/pmean return the
    cell's own contribution unchanged and allgather broadcasts it to the
    gathered shape -- same aval as the real reduction, zero bytes on the
    wire.  The numerics are wrong on purpose; a program built with this
    executor (``EngineProgram.local_step``) is what the benchmark's
    ``exchange_left_out`` fault plants, so that its correctness check
    is shown to catch a solver that skips its collectives.
    """

    def _exec(self, point: Collective, value):
        if point.op == "allgather":
            value = jnp.asarray(value)
            return jnp.broadcast_to(
                value[None], (self.sizes[point.axis],) + value.shape)
        return value


class ShapeProbeComm(Comm):
    """Collective-free executor that records each point's per-cell result
    aval (and, optionally, its per-cell *payload* aval -- the input the
    cell hands to ``comm``, which is what travels the wire and what an
    error-feedback residual must match).  Used once at build time (under
    ``jax.eval_shape``, OUTSIDE any mesh/vmap axis context) so the
    engines can allocate staleness rings / EF buffers and price the
    wire before the first step.  psum/pmean preserve the per-cell
    shape; allgather prepends the axis extent.
    """

    def __init__(self, schedule, axis_map, sizes, record: dict,
                 payloads: Optional[dict] = None):
        super().__init__(schedule, axis_map, sizes)
        self._record = record
        self._payloads = payloads if payloads is not None else {}

    def axis_index(self, axis: str):
        # no axis context under eval_shape; any in-range index has the
        # right aval (indices only feed PRNG folds / slice starts)
        return jnp.zeros((), jnp.int32)

    def _exec(self, point, value):
        value = jnp.asarray(value)
        self._payloads[point.name] = jax.ShapeDtypeStruct(
            value.shape, value.dtype)
        if point.op == "allgather":
            out = jnp.broadcast_to(
                value[None], (self.sizes[point.axis],) + value.shape)
        else:
            out = value
        self._record[point.name] = jax.ShapeDtypeStruct(out.shape, out.dtype)
        return out


class StaleComm(SyncComm):
    """Bounded-staleness executor (the async engine's policy).

    The reduction result *applied* at outer step t is the one *computed*
    at step ``max(1, t - tau)``.  Each point carries a ``(tau, ...)``
    FIFO ring in the engine state: slot ``(t-1) % tau`` holds the
    reduction of step ``t - tau``, which is read just before the fresh
    value overwrites it.

    **Warm-up semantics (pinned by tests/test_comm.py):** at t = 1 every
    ring slot is seeded with the *first* reduction, so the first ``tau``
    steps consume the reduction of step ``max(1, t - tau)`` -- i.e.
    steps 1..tau+1 all consume step 1's value, never zeros from
    initialization and never a partially-filled ring.  This is the same
    contract the overlap engine needs: during warm-up there is nothing
    in flight to await, so the dispatch of step 1 is the only value
    available.

    The fresh collective still executes every step -- on real hardware
    the reduction would be launched asynchronously and *consumed* tau
    steps later; semantically (and for convergence studies, which is
    what this engine is for) only the consumption delay matters.

    ``tau = 0`` never touches a buffer and returns the fresh value, so
    the async engine at zero staleness is the sync engine, bit for bit.

    ``wire_bytes`` accounting is **additive, not policy-dependent**: the
    ring only re-times consumption, every step still puts exactly one
    payload per declared point on the wire, so sync / stale / overlap
    report identical byte totals for the identity codec (tested).
    """

    def __init__(self, schedule, axis_map, sizes, *, tau: int, t,
                 bufs: Optional[dict] = None):
        super().__init__(schedule, axis_map, sizes)
        if tau < 0:
            raise ValueError(f"staleness tau={tau} must be >= 0")
        self.tau = int(tau)
        self.t = t                         # traced outer-iteration counter
        self.bufs_in = bufs or {}

    def _exec(self, point, value):
        # the wire op goes through the _reduce hook so the hierarchical
        # two-level reduction composes with the staleness ring
        fresh = self._reduce(point, value)
        if self.tau == 0:
            return fresh
        try:
            buf = self.bufs_in[point.name]   # (tau, *cell result shape)
        except KeyError:
            raise KeyError(
                f"no staleness buffer for reduction {point.name!r}; the "
                "async engine allocates one per declared point at build "
                "time -- was the schedule changed after program "
                "construction?") from None
        slot = (self.t - 1) % self.tau
        stale = jax.lax.dynamic_index_in_dim(buf, slot, 0, keepdims=False)
        first = self.t == 1
        stale = jnp.where(first, fresh, stale)
        updated = jax.lax.dynamic_update_index_in_dim(
            buf, fresh.astype(buf.dtype), slot, 0)
        seeded = jnp.broadcast_to(fresh, buf.shape).astype(buf.dtype)
        self.bufs_out[point.name] = jnp.where(first, seeded, updated)
        return stale

    def finalize(self):
        super().finalize()
        if self.tau and set(self.bufs_out) != set(self.schedule.names):
            raise ValueError("staleness buffers out of sync with schedule")


class OverlapComm(StaleComm):
    """Communication-overlap executor (the overlap engine's policy).

    Same consumption contract as :class:`StaleComm` -- the value applied
    at step t is the reduction *dispatched* at step ``max(1, t - tau)``
    -- but the engine built around it actually lets the wire overlap
    the local solve:

      * inside the jitted step the ring slots are the *reduction
        in-flight buffers*: the fresh collective's result is written to
        the slot that will be consumed tau steps later and nothing
        downstream of this step's local solve depends on it, so XLA's
        latency-hiding scheduler is free to run the collective
        concurrently with the cell-local SDCA/SVRG kernels of steps
        t..t+tau.  The engine donates the ring buffers to the step
        (double-buffered slots, no defensive copy) to keep that window
        open on accelerator backends;
      * on the host path the driver never calls ``block_until_ready``
        on the rings between steps -- only the iterate substate is
        synced at observation points (``EngineProgram.sync_of``), so
        dispatch returns a future and the await happens tau steps
        later when the slot is next read.

    Because consumption timing is identical to :class:`StaleComm`, the
    overlap engine's trajectories match the async engine at equal tau
    (and the sync engine bit-for-bit at tau = 0): overlap changes
    *wall-clock*, never numerics.  Error-feedback residuals of a
    composed :class:`CompressedComm` live with the **dispatch** step by
    construction -- the codec encodes the payload before ``_reduce``
    ever sees it, so the residual written to the engine state at step t
    is the one produced by the payload dispatched at step t.
    """

    #: engines key off this to enable donation + selective host sync
    overlap = True


def hier_ef_names(schedule: CommSchedule, topology) -> Tuple[str, ...]:
    """Names of collectives that need a cross-pod error-feedback
    residual under ``topology``: the psum/pmean points over the
    pod-split axis, when the cross-pod codec is stateful."""
    if topology is None or topology.pods <= 1:
        return ()
    from .compress import get_codec
    if not get_codec(topology.codec).stateful:
        return ()
    return tuple(p.name for p in schedule
                 if p.axis == topology.axis and p.op != "allgather")
