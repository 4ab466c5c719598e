"""Engine executors for the unified solver framework (``repro.core.solver``).

An *engine* is how the P x Q block grid of the paper is executed.  Since
Engine API v2 each solver contributes ONE :class:`CellProgram` -- its
per-cell step math plus a :class:`~repro.core.comm.CommSchedule`
declaring every cross-cell reduction as a named collective -- and the
engines here execute that single program three ways:

  * ``"simulated"``  -- :func:`grid_program`: the grid is the leading
    axes of blocked arrays and cells run under nested *named* ``vmap``
    on one device; the declared collectives become vmap-axis reductions
    (correctness tests, the one-chip benchmark cells);
  * ``"shard_map"``  -- :func:`mesh_program`: a (data=P, model=Q) device
    mesh where each device owns one (n_p, m_q) block in HBM and the
    collectives are mesh reductions, applied synchronously (the
    production path);
  * ``"async"``      -- :func:`mesh_program` with ``staleness=tau``: the
    same mesh execution under a :class:`~repro.core.comm.StaleComm`,
    which applies every declared reduction with bounded staleness tau
    via FIFO buffers carried in the engine state.  ``tau = 0``
    reproduces ``"shard_map"`` exactly (same jaxpr).

Orthogonally to the engine choice, a
:class:`~repro.core.compress.CompressionPolicy` (``compression=``)
routes every declared collective's payload through a codec with error
feedback (:class:`~repro.core.compress.CompressedComm` wraps the
sync/stale executor), and every binding reports exact bytes-on-wire
via :func:`comm_accounting` (``EngineProgram.comm_bytes``).

The executors produce an :class:`EngineProgram` -- initial state, jitted
outer step, extractors for the global primal (and dual) iterates.
Everything else (the outer loop, history, early stopping, warm starts)
lives once in the shared driver.

All engines pad the feature dimension to a multiple of P*Q (columns of
zeros are inert under every update rule), so a cell sees bit-identical
blocks regardless of engine and the executions agree to float
tolerance.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from ..obs.trace import PROFILER_TRACER, as_tracer
from .comm import (CommSchedule, LocalComm, OverlapComm, ShapeProbeComm,
                   StaleComm, SyncComm, hier_ef_names)
from .comm_model import hierarchical_accounting
from .compress import CompressedComm, get_codec, wire_accounting
from .partition import _ceil_to
from .util import as_axes, axes_size, host_nbytes, pvary, shard_map


@dataclasses.dataclass
class EngineProgram:
    """One algorithm bound to one engine: state + step + extractors.

    The uniform handle ``Solver.program`` returns and ``drive`` runs.

    Attributes:
      state: the initial engine-state pytree (blocked iterates plus any
        communication state -- staleness rings, EF residuals).
      step: jitted ``(t, state) -> state`` advancing one outer
        iteration; ``t`` is the 1-based iteration counter.
      w_of: ``state -> (m,)`` -- the assembled global primal iterate
        (trimmed of any grid padding).
      alpha_of: ``state -> (n,)`` global dual, or None for primal-only
        solvers.

    The remaining fields are engine metadata the driver and telemetry
    key off (documented inline below).
    """

    state: Any
    step: Callable[[int, Any], Any]
    w_of: Callable[[Any], jnp.ndarray]
    alpha_of: Optional[Callable[[Any], jnp.ndarray]] = None
    #: exact per-step wire accounting of the program's declared
    #: collectives (see ``repro.core.compress.wire_accounting``); None
    #: for programs built outside the generic executors
    comm_bytes: Optional[dict] = None
    #: same cell program with every collective executed cell-locally
    #: (:class:`~repro.core.comm.LocalComm`); jitted lazily, so it costs
    #: nothing unless called.  Numerically wrong by design: it is the
    #: program that leaves out its exchanges, which the benchmark's
    #: ``exchange_left_out`` fault plants to show its check catches one
    local_step: Optional[Callable[[int, Any], Any]] = None
    #: state -> {collective: error-feedback residual array} when the
    #: compression policy carries stateful codecs (telemetry reads the
    #: per-iteration EF norms off it); None otherwise
    ef_of: Optional[Callable[[Any], dict]] = None
    #: consumption delay tau the program was built with (0 = sync)
    staleness: int = 0
    #: True for the overlap engine: reductions are dispatched into
    #: double-buffered ring slots and awaited tau steps later, so the
    #: driver must not block on in-flight comm state between steps
    overlap: bool = False
    #: state -> the substate that must be device-complete at an
    #: observation point (the iterate substate, EXCLUDING in-flight
    #: reduction slots).  None means block on the whole state -- the
    #: overlap engine sets this so ``drive`` keeps the dispatch window
    #: open on the host path
    sync_of: Optional[Callable[[Any], Any]] = None
    #: True when ``step`` donates its state argument (overlap engine on
    #: non-CPU backends): callers that re-step from a saved state must
    #: copy it first
    donated: bool = False
    #: ``w -> F(w)`` and ``alpha -> D(alpha)`` of the global iterates
    #: (what ``w_of`` / ``alpha_of`` give), evaluated on the training
    #: blocks the program already holds on the device.  None: the solve
    #: loop evaluates on the caller's X instead
    primal_of: Optional[Callable[[Any], jnp.ndarray]] = None
    dual_of: Optional[Callable[[Any], jnp.ndarray]] = None


def drive(prog: EngineProgram, outer_iters: int, observe=None, *,
          tracer=None, on_step=None, monitor=None):
    """Run the outer loop.  ``observe(t, state) -> bool`` is called after
    every step; returning True stops early.  Returns
    (final state, iterations run, stopped_early).

    Each iteration is a ``repro.iter`` span holding ``repro.step`` (the
    step's dispatch) and ``repro.observe`` spans, each with its ``iter``.

      * ``tracer`` -- a :class:`repro.obs.trace.Tracer`; default the
        profiler-only :data:`~repro.obs.trace.PROFILER_TRACER`, under
        which the loop adds no sync and the device side of a step is its
        own module in a profile, on the same clock.  A tracer that keeps
        events (``enabled``) makes each step block on its device result,
        so its ``repro.step`` measures the device step;
      * ``on_step(t, step_s)`` -- fires after every step, which is then
        timed and blocked on (the solver driver feeds its registry and
        the per-iter history fields from it);
      * ``monitor`` -- a :class:`repro.obs.health.HealthMonitor`; its
        rate-limited ``poll()`` runs once per iteration (a clock read
        when not due -- health rules only *read* the registry, so the
        iterates are untouched).

    Spans, timing and blocking never change the iterates: every path
    runs the same steps in the same order.
    """
    tr = as_tracer(tracer, PROFILER_TRACER)
    timed = tr.enabled or on_step is not None
    clock = tr.clock if tr.enabled else time.perf_counter
    state = prog.state
    # The overlap engine's contract: never block on in-flight reduction
    # slots between steps -- only the iterate substate is synced, so a
    # dispatched collective stays a future until the slot is read tau
    # steps later.  sync_of is None for every other engine (block on
    # the whole state, the pre-overlap behavior).
    sync = prog.sync_of if prog.sync_of is not None else (lambda s: s)
    for t in range(1, outer_iters + 1):
        with tr.span("repro.iter", iter=t):
            with tr.span("repro.step", iter=t):
                if timed:
                    t0 = clock()
                    state = prog.step(t, state)
                    jax.block_until_ready(sync(state))
                    step_s = clock() - t0
                else:
                    state = prog.step(t, state)
            if on_step is not None:
                on_step(t, step_s)
            if monitor is not None:
                monitor.poll()
            if observe is not None:
                with tr.span("repro.observe", iter=t):
                    stop = observe(t, state)
                if stop:
                    return state, t, True
    return state, outer_iters, False


def drive_with_callback(prog: EngineProgram, outer_iters: int, callback=None,
                        pass_alpha: bool = False):
    """Driver for the legacy ``*_simulated`` / ``*_distributed`` wrappers:
    relay each iterate to ``callback(t, w[, alpha])``, ignoring its return
    value (legacy callbacks never early-stop).  Returns the final state."""
    observe = None
    if callback is not None:
        def observe(t, state):
            if pass_alpha:
                callback(t, prog.w_of(state), prog.alpha_of(state))
            else:
                callback(t, prog.w_of(state))
            return False
    state, _, _ = drive(prog, outer_iters, observe)
    return state


# ---------------------------------------------------------------------------
# shard_map data preparation
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ShardMapData:
    """Padded global arrays placed on a (data=P, model=Q) mesh."""

    mesh: Any
    x: jnp.ndarray          # (n_pad, m_pad)  sharded (data, model)
    y: jnp.ndarray          # (n_pad,)        sharded (data,)
    mask: jnp.ndarray       # (n_pad,)        sharded (data,)
    n: int                  # true observation count
    m: int                  # true feature count
    P: int
    Q: int
    data_axis: Any = "data"
    model_axis: str = "model"

    @property
    def n_pad(self) -> int:
        return self.x.shape[0]

    @property
    def m_pad(self) -> int:
        return self.x.shape[1]

    @property
    def n_p(self) -> int:
        return self.x.shape[0] // self.P

    @property
    def m_q(self) -> int:
        return self.x.shape[1] // self.Q

    def put(self, arr, spec):
        """device_put onto this mesh with the given PartitionSpec."""
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def zeros_data(self):
        return self.put(jnp.zeros((self.n_pad,)), P(self.data_axis))

    def zeros_model(self):
        return self.put(jnp.zeros((self.m_pad,)), P(self.model_axis))

    def pad_w(self, w):
        wp = np.zeros((self.m_pad,), np.float32)
        wp[: self.m] = np.asarray(w, np.float32)
        return self.put(jnp.asarray(wp), P(self.model_axis))

    def pad_alpha(self, alpha):
        ap = np.zeros((self.n_pad,), np.float32)
        ap[: self.n] = np.asarray(alpha, np.float32)
        return self.put(jnp.asarray(ap), P(self.data_axis))


@dataclasses.dataclass(frozen=True)
class SparseShardMapData:
    """Padded-ELL global arrays placed on a (data=P, model=Q) mesh.

    The (n_pad, Q*k) ``cols``/``vals`` arrays are sharded
    (data, model): device (p, q) holds exactly the (n_p, k) ELL cell of
    block (p, q), with block-LOCAL column ids in [0, m_q).  Device
    memory for the data block is O(n_p * k) ~ O(nnz), not O(n_p * m_q).
    """

    mesh: Any
    cols: jnp.ndarray       # (n_pad, Q*k) int32  sharded (data, model)
    vals: jnp.ndarray       # (n_pad, Q*k) f32    sharded (data, model)
    y: jnp.ndarray          # (n_pad,)            sharded (data,)
    mask: jnp.ndarray       # (n_pad,)            sharded (data,)
    n: int                  # true observation count
    m: int                  # true feature count
    m_q: int                # padded feature-block width (m_pad = Q * m_q)
    P: int
    Q: int
    data_axis: Any = "data"
    model_axis: str = "model"

    @property
    def n_pad(self) -> int:
        return self.cols.shape[0]

    @property
    def m_pad(self) -> int:
        return self.Q * self.m_q

    @property
    def n_p(self) -> int:
        return self.cols.shape[0] // self.P

    @property
    def k(self) -> int:
        return self.cols.shape[1] // self.Q

    def put(self, arr, spec):
        return jax.device_put(arr, NamedSharding(self.mesh, spec))

    def zeros_data(self):
        return self.put(jnp.zeros((self.n_pad,)), P(self.data_axis))

    def zeros_model(self):
        return self.put(jnp.zeros((self.m_pad,)), P(self.model_axis))

    def pad_w(self, w):
        wp = np.zeros((self.m_pad,), np.float32)
        wp[: self.m] = np.asarray(w, np.float32)
        return self.put(jnp.asarray(wp), P(self.model_axis))

    def pad_alpha(self, alpha):
        ap = np.zeros((self.n_pad,), np.float32)
        ap[: self.n] = np.asarray(alpha, np.float32)
        return self.put(jnp.asarray(ap), P(self.data_axis))


def prepare_shard_map_sparse(mesh, X, y, *, data_axis="data",
                             model_axis="model",
                             m_multiple: int | None = None,
                             k_multiple: int = 8,
                             tracer=None) -> SparseShardMapData:
    """Sparse analogue of :func:`prepare_shard_map`.

    ``X`` is a :class:`~repro.data.sparse.CSRMatrix` (or a dense array,
    converted).  Padding matches ``partition_sparse`` bit-for-bit, so a
    shard_map cell sees the same ELL block as the simulated grid's cell.
    The host's ELL build is a ``repro.prep.partition`` span with the ELL
    counters, the puts a ``repro.prep.transfer`` span, in ``tracer``
    (default the profiler-only tracer).
    """
    from repro.data.sparse import CSRMatrix, csr_from_dense
    from .partition import _ceil_to as ceil_to, _ell_blocks
    tr = as_tracer(tracer, PROFILER_TRACER)
    Pn = axes_size(mesh, data_axis)
    Qn = axes_size(mesh, model_axis)
    if m_multiple is not None and m_multiple % Qn:
        raise ValueError(f"m_multiple={m_multiple} not a multiple of Q={Qn}")
    with tr.span("repro.prep.partition") as span:
        if not isinstance(X, CSRMatrix):
            X = csr_from_dense(np.asarray(X))
        n, m = X.shape
        m_pad = ceil_to(m, m_multiple or Qn)
        cols, vals, y_blocks, mask_blocks, counters = _ell_blocks(
            X, y, Pn, Qn, m_pad, k_multiple)
        span.set_metadata(**counters)
        # (n_pad, Q, k) -> (P*n_p, Q*k), a view: block (p, q) lands at the
        # [p*n_p:(p+1)*n_p, q*k:(q+1)*k] tile, which the (data, model)
        # sharding assigns to device (p, q)
        cols_g = cols.reshape(cols.shape[0], -1)
        vals_g = vals.reshape(vals.shape[0], -1)
    daxes = as_axes(data_axis)
    put = _putter(mesh)
    with tr.span("repro.prep.transfer",
                 bytes=host_nbytes(cols_g, vals_g, y_blocks, mask_blocks)):
        return SparseShardMapData(
            mesh=mesh,
            cols=put(jnp.asarray(cols_g), P(daxes, model_axis)),
            vals=put(jnp.asarray(vals_g), P(daxes, model_axis)),
            y=put(jnp.asarray(y_blocks.reshape(-1)), P(daxes)),
            mask=put(jnp.asarray(mask_blocks.reshape(-1)), P(daxes)),
            n=n, m=m, m_q=m_pad // Qn, P=Pn, Q=Qn,
            data_axis=data_axis, model_axis=model_axis)


def _putter(mesh):
    def put(a, spec):
        return jax.device_put(a, NamedSharding(mesh, spec))
    return put


# ---------------------------------------------------------------------------
# Engine API v2: one CellProgram per solver, executed by generic engines
# ---------------------------------------------------------------------------

#: a *dim-spec* annotates one operand: a tuple over its leading array
#: dims naming the logical grid axis each dim is split over ("data",
#: "model", or None for unsplit dims); trailing dims are unsplit.  The
#: same spec drives the shard_map PartitionSpec, the grid engine's vmap
#: in_axes, and which axes an input must be pvary-promoted over.
DimSpec = Tuple[Optional[str], ...]


def _is_dimspec(x) -> bool:
    return isinstance(x, tuple) and all(
        e is None or isinstance(e, str) for e in x)


def _spec_leaves(specs):
    return jax.tree_util.tree_leaves(specs, is_leaf=_is_dimspec)


@dataclasses.dataclass(frozen=True)
class CellProgram:
    """One solver's per-cell step math plus its communication contract.

    ``cell(comm, t, data, state) -> state`` operates on PER-CELL arrays
    (the (n_p, m_q) block a device owns) and performs every cross-cell
    reduction through the :class:`~repro.core.comm.Comm` it is handed --
    never via inline ``lax.psum``.  ``data_specs`` / ``state_specs`` are
    pytrees matching ``data`` / ``state`` whose leaves are dim-specs
    (see :data:`DimSpec`).  One CellProgram serves every engine.
    """

    schedule: CommSchedule
    cell: Callable[..., Any]
    data_specs: Any
    state_specs: Any


# -- grid engine (named vmap on one device) ---------------------------------

_GRID_DATA, _GRID_MODEL = "grid_data", "grid_model"
_GRID_POD = "grid_pod"

#: grid-engine error-feedback dict key prefix for the cross-pod
#: (topology) codec residuals -- keeps them distinct from a
#: CompressionPolicy residual on the same collective name inside the
#: single blocked ``ef`` operand
_POD_EF = "pod:"


def _norm_topology(topology):
    """None | spec | Topology -> Topology with pods > 1, else None."""
    if topology is None:
        return None
    from .comm_model import Topology
    topo = Topology.from_spec(topology)
    if topo.pods <= 1:
        return None
    if topo.axis != "data":
        raise ValueError(f"topology splits axis {topo.axis!r}; the engines "
                         "only pod-split the 'data' axis")
    return topo


def _split_pods(tree, specs, G):
    """Blocked layout -> pod-split blocked layout: every leaf whose
    dim-spec names 'data' splits its leading P block axis into
    (G, P // G).  Pods are contiguous index ranges, matching the
    mesh engines' ("pod", "data") axis order and ``axes_index``."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    out = []
    for leaf, ds in zip(leaves, _spec_leaves(specs)):
        if "data" in ds:
            leaf = leaf.reshape((G, leaf.shape[0] // G) + leaf.shape[1:])
        out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def _merge_pods(tree):
    """Collapse the (G, P // G) leading axes every vmap output carries
    back into one P axis (all out leaves are stacked over all levels)."""
    return jax.tree_util.tree_map(
        lambda leaf: leaf.reshape((-1,) + leaf.shape[2:]), tree)


def _drop_replicas(out, state_specs):
    """Collectives replicate results along the reduced axis exactly
    (every cell sees the same psum), so dropping replicas is exact."""
    leaves, treedef = jax.tree_util.tree_flatten(out)
    spec_leaves = _spec_leaves(state_specs)
    kept = []
    for leaf, ds in zip(leaves, spec_leaves):
        if "data" not in ds:
            leaf = leaf[0]
            if "model" not in ds:
                leaf = leaf[0]
        elif "model" not in ds:
            leaf = leaf[:, 0]
        kept.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, kept)


def cached_build(cache, key, build):
    """Memoize ``build()`` under ``key`` in ``cache`` (a plain dict owned
    by the caller); ``cache=None`` just calls ``build()``.

    The program builders use this to reuse their jitted step callables
    across repeated builds with constant shapes (the online update loop,
    the fleet's sequential baseline): a reused ``jax.jit`` object hits
    the compiled-executable cache instead of re-tracing from scratch.
    """
    if cache is None:
        return build()
    if key not in cache:
        cache[key] = build()
    return cache[key]


def grid_program(cellprog: CellProgram, Pn: int, Qn: int, *,
                 compression=None, comm_local: bool = False,
                 topology=None):
    """Named-``vmap`` executor: the P x Q grid is the leading block axes
    of the operands and the declared collectives run as vmap-axis
    reductions.  Returns a jitted ``step(t, data, state) -> state``
    where ``data``/``state`` are BLOCKED pytrees: each leaf carries one
    leading block axis per logical axis in its dim-spec, in
    (data, model) order, with the per-cell extent left in place (so a
    cell sees exactly the array a shard_map device would own).

    With ``compression`` (a validated
    :class:`~repro.core.compress.CompressionPolicy`) the step signature
    becomes ``step(t, data, (state, ef)) -> (state, ef)``: every
    collective payload runs through its codec under a
    :class:`~repro.core.compress.CompressedComm`, and ``ef`` maps each
    compressed collective to its (P, Q, *payload) error-feedback
    residuals (allocate with :func:`grid_comm_state`).  ``None`` builds
    the exact uncompressed program.

    ``comm_local=True`` substitutes :class:`~repro.core.comm.LocalComm`
    for the sync executor: every collective runs cell-locally, same
    avals, zero reduction work.  Timing-only (``EngineProgram.
    local_step``); incompatible with ``compression`` (a local program's
    wire cost is zero by construction).

    ``topology`` (a :class:`~repro.core.comm_model.Topology` or spec
    string with ``pods > 1``) pod-splits the data axis as a THIRD named
    vmap level, so psums over "data" execute hierarchically (intra-pod
    full precision, cross-pod through the topology codec).  The step
    then always takes the ``(state, ef)`` full state (cross-pod EF
    residuals ride in ``ef`` under ``"pod:"``-prefixed keys) and the
    blocked operand layout is unchanged -- pods are contiguous P-index
    ranges reshaped inside the step.
    """
    topo = _norm_topology(topology)
    if comm_local:
        topo = None            # the local twin runs no reductions at all
    axis_map = {"data": (_GRID_DATA,), "model": (_GRID_MODEL,)}
    G = 1
    if topo is not None:
        G = topo.pods
        if Pn % G:
            raise ValueError(f"topology pods={G} does not divide P={Pn}")
        axis_map = {"data": (_GRID_POD, _GRID_DATA),
                    "model": (_GRID_MODEL,)}
    sizes = {"data": Pn, "model": Qn}
    sched = cellprog.schedule
    policy = compression
    if comm_local and policy is not None:
        raise ValueError("comm_local measures the collective-free step; "
                         "it cannot compose with a compression policy")
    if policy is not None:
        policy.validate(sched)
    comm_cls = LocalComm if comm_local else SyncComm
    hier_codec = get_codec(topo.codec) if topo is not None else None

    def in_axes(specs, axis):
        return jax.tree_util.tree_map(
            lambda ds: 0 if axis in ds else None, specs,
            is_leaf=_is_dimspec)

    if policy is None and topo is None:
        def one_cell(t, d, s):
            comm = comm_cls(sched, axis_map, sizes)
            out = cellprog.cell(comm, t, d, s)
            comm.finalize()
            return out

        inner = jax.vmap(one_cell,
                         in_axes=(None, in_axes(cellprog.data_specs, "model"),
                                  in_axes(cellprog.state_specs, "model")),
                         axis_name=_GRID_MODEL)
        outer = jax.vmap(inner,
                         in_axes=(None, in_axes(cellprog.data_specs, "data"),
                                  in_axes(cellprog.state_specs, "data")),
                         axis_name=_GRID_DATA)

        def step(t, data, state):
            out = outer(t, data, state)     # every leaf gains (P, Q) leading
            return _drop_replicas(out, cellprog.state_specs)

        return jax.jit(step)

    def one_cell_c(t, d, s, ef):
        inner = SyncComm(sched, axis_map, sizes)
        if topo is not None:
            inner.set_topology(
                topo, hier_codec,
                ef={k[len(_POD_EF):]: v for k, v in ef.items()
                    if k.startswith(_POD_EF)})
        if policy is not None:
            comm = CompressedComm(
                inner, policy,
                ef={k: v for k, v in ef.items()
                    if not k.startswith(_POD_EF)})
        else:
            comm = inner
        out = cellprog.cell(comm, t, d, s)
        comm.finalize()
        ef_out = dict(comm.ef_out) if policy is not None else {}
        if topo is not None:
            ef_out.update({_POD_EF + k: v
                           for k, v in inner.hier_ef_out.items()})
        return out, ef_out

    # EF residuals are private per cell: blocked over every grid axis
    vm = jax.vmap(one_cell_c,
                  in_axes=(None, in_axes(cellprog.data_specs, "model"),
                           in_axes(cellprog.state_specs, "model"), 0),
                  axis_name=_GRID_MODEL)
    vm = jax.vmap(vm,
                  in_axes=(None, in_axes(cellprog.data_specs, "data"),
                           in_axes(cellprog.state_specs, "data"), 0),
                  axis_name=_GRID_DATA)
    if topo is not None:
        vm = jax.vmap(vm,
                      in_axes=(None, in_axes(cellprog.data_specs, "data"),
                               in_axes(cellprog.state_specs, "data"), 0),
                      axis_name=_GRID_POD)

    def step_c(t, data, full_state):
        state, ef = full_state
        if G > 1:
            data = _split_pods(data, cellprog.data_specs, G)
            state = _split_pods(state, cellprog.state_specs, G)
            ef = {k: v.reshape((G, v.shape[0] // G) + v.shape[1:])
                  for k, v in ef.items()}
        out, ef_out = vm(t, data, state, ef)
        if G > 1:
            out = _merge_pods(out)
            ef_out = _merge_pods(ef_out)
        return _drop_replicas(out, cellprog.state_specs), ef_out

    return jax.jit(step_c)


# -- mesh engines (shard_map; sync and bounded-staleness) -------------------

def _mesh_pspec(ds: DimSpec, daxes, model_axis):
    entries = []
    for a in ds:
        if a == "data":
            entries.append(daxes if len(daxes) > 1 else daxes[0])
        elif a == "model":
            entries.append(model_axis)
        else:
            entries.append(None)
    return P(*entries)


def _pvary_missing(tree_vals, specs, axis_map):
    """Promote operands to fully varying over the mesh axes their
    dim-spec does not split them over (replicated inputs must be
    promoted before mixing with varying values on recent JAX)."""
    leaves, treedef = jax.tree_util.tree_flatten(tree_vals)
    out = []
    for v, ds in zip(leaves, _spec_leaves(specs)):
        missing = ()
        if "data" not in ds:
            missing += axis_map["data"]
        if "model" not in ds:
            missing += axis_map["model"]
        out.append(pvary(v, missing))
    return jax.tree_util.tree_unflatten(treedef, out)


def mesh_step_fn(cellprog: CellProgram, mesh, *, data_axis="data",
                 model_axis: str = "model", staleness: int = 0,
                 compression=None, comm_local: bool = False,
                 overlap: bool = False, topology=None):
    """Raw (unjitted) mesh executor.

    Returns ``step(t, data, state, cbufs) -> (state, cbufs)`` running
    the cell once per device of the (data=P, model=Q) mesh under
    shard_map.  ``cbufs`` is the communication-state pytree -- ``{}``
    when no policy needs state, otherwise up to three sub-dicts of
    per-cell buffers sharded over (data, model):

      * ``cbufs["stale"]`` (``staleness = tau > 0``): one
        ``(P, Q, tau, *cell_result_shape)`` FIFO ring per collective
        (:class:`StaleComm`, or :class:`OverlapComm` when
        ``overlap=True`` -- same numerics, but the ring slots double as
        the in-flight reduction buffers the engine donates; tau = 0
        applies every reduction synchronously via :class:`SyncComm`);
      * ``cbufs["ef"]`` (``compression`` with lossy codecs): one
        ``(P, Q, *payload_shape)`` f32 error-feedback residual per
        compressed collective (:class:`CompressedComm` wrapping the
        sync/stale executor, so compression composes with staleness);
      * ``cbufs["hier_ef"]`` (``topology`` with pods > 1 and a stateful
        cross-pod codec): one ``(P, Q, *payload_shape)`` f32 residual
        per pod-split collective for the hierarchical two-level
        reduction.  ``data_axis`` must then be a >= 2 axis tuple with
        the pod axis leading (e.g. ``("pod", "data")``).
    """
    topo = _norm_topology(topology)
    if comm_local:
        topo = None            # the local twin runs no reductions at all
    daxes = as_axes(data_axis)
    axis_map = {"data": daxes, "model": (model_axis,)}
    sizes = {"data": axes_size(mesh, data_axis),
             "model": axes_size(mesh, model_axis)}
    sched = cellprog.schedule
    policy = compression
    if comm_local and (staleness or policy is not None):
        raise ValueError("comm_local measures the collective-free step; "
                         "it cannot compose with staleness or compression")
    if policy is not None:
        policy.validate(sched)
    ef_names = policy.stateful_names(sched) if policy is not None else ()
    if topo is not None:
        if len(daxes) < 2:
            raise ValueError(
                f"topology pods={topo.pods} needs a pod-split mesh: pass "
                f"data_axis as a >= 2 axis tuple, got {data_axis!r}")
        if axes_size(mesh, daxes[:1]) != topo.pods:
            raise ValueError(
                f"mesh pod axis {daxes[0]!r} has extent "
                f"{axes_size(mesh, daxes[:1])}, topology says "
                f"pods={topo.pods}")
    hier_codec = get_codec(topo.codec) if topo is not None else None
    hnames = hier_ef_names(sched, topo)
    dspec = daxes if len(daxes) > 1 else daxes[0]

    def pspecs(specs):
        return jax.tree_util.tree_map(
            lambda ds: _mesh_pspec(ds, daxes, model_axis), specs,
            is_leaf=_is_dimspec)

    data_pspecs = pspecs(cellprog.data_specs)
    state_pspecs = pspecs(cellprog.state_specs)
    buf_pspecs = {}
    if staleness:
        buf_pspecs["stale"] = {name: P(dspec, model_axis)
                               for name in sched.names}
    if ef_names:
        buf_pspecs["ef"] = {name: P(dspec, model_axis) for name in ef_names}
    if hnames:
        buf_pspecs["hier_ef"] = {name: P(dspec, model_axis)
                                 for name in hnames}

    def kernel(t, data, state, cbufs):
        data = _pvary_missing(data, cellprog.data_specs, axis_map)
        state = _pvary_missing(state, cellprog.state_specs, axis_map)
        t = pvary(t, daxes + (model_axis,))
        if staleness:
            stale_cls = OverlapComm if overlap else StaleComm
            inner = stale_cls(sched, axis_map, sizes, tau=staleness, t=t,
                              bufs={k: b[0, 0]
                                    for k, b in cbufs["stale"].items()})
        else:
            inner = (LocalComm if comm_local else SyncComm)(
                sched, axis_map, sizes)
        if topo is not None:
            inner.set_topology(topo, hier_codec,
                               ef={k: b[0, 0]
                                   for k, b in cbufs.get("hier_ef",
                                                         {}).items()})
        if policy is not None:
            comm = CompressedComm(inner, policy,
                                  ef={k: b[0, 0]
                                      for k, b in cbufs.get("ef",
                                                            {}).items()})
        else:
            comm = inner
        out = cellprog.cell(comm, t, data, state)
        comm.finalize()
        cb_out = {}
        if staleness:
            cb_out["stale"] = {k: b[None, None]
                               for k, b in comm.bufs_out.items()}
        if ef_names:
            cb_out["ef"] = {k: e[None, None]
                            for k, e in comm.ef_out.items()}
        if hnames:
            cb_out["hier_ef"] = {k: e[None, None]
                                 for k, e in inner.hier_ef_out.items()}
        return out, cb_out

    return shard_map(
        kernel, mesh,
        in_specs=(P(), data_pspecs, state_pspecs, buf_pspecs),
        out_specs=(state_pspecs, buf_pspecs))


def probe_collective_shapes(cellprog: CellProgram, data, state, *,
                            sizes, layout: str = "global"):
    """Per-cell avals of every declared collective, via one
    ``eval_shape`` trace of the cell under a ShapeProbeComm (no mesh or
    devices needed).  Returns ``(results, payloads)``: the *result* aval
    sizes the async engine's staleness rings; the *payload* aval (the
    value the cell hands to ``comm``, i.e. what travels the wire) sizes
    error-feedback residuals and the wire accounting.

    ``layout`` names how ``data``/``state`` leaves relate to one cell's
    array: ``"global"`` (mesh layout -- each dim named in the dim-spec
    is divided by its grid extent) or ``"blocked"`` (grid-engine layout
    -- one extra leading block axis per named dim, dropped).
    """
    if layout not in ("global", "blocked"):
        raise ValueError(f"layout={layout!r}; expected 'global' or "
                         "'blocked'")

    def cell_aval(arr, ds):
        arr = jnp.asarray(arr) if not hasattr(arr, "shape") else arr
        if layout == "blocked":
            k = sum(1 for a in ds if a)
            return jax.ShapeDtypeStruct(tuple(arr.shape[k:]), arr.dtype)
        shape = list(arr.shape)
        for i, a in enumerate(ds):
            if a:
                shape[i] //= sizes[a]
        return jax.ShapeDtypeStruct(tuple(shape), arr.dtype)

    def avals(tree_vals, specs):
        leaves, treedef = jax.tree_util.tree_flatten(tree_vals)
        out = [cell_aval(v, ds)
               for v, ds in zip(leaves, _spec_leaves(specs))]
        return jax.tree_util.tree_unflatten(treedef, out)

    record: dict = {}
    payloads: dict = {}
    probe = ShapeProbeComm(cellprog.schedule,
                           {"data": ("data",), "model": ("model",)}, sizes,
                           record, payloads)

    def run(t, d, s):
        out = cellprog.cell(probe, t, d, s)
        probe.finalize()
        return out

    jax.eval_shape(run, jax.ShapeDtypeStruct((), jnp.int32),
                   avals(data, cellprog.data_specs),
                   avals(state, cellprog.state_specs))
    return record, payloads


def comm_accounting(cellprog: CellProgram, data, state, *, sizes,
                    layout: str = "global", compression=None) -> dict:
    """Exact per-step bytes-on-wire of a CellProgram's schedule under a
    compression policy (None = uncompressed), for
    ``EngineProgram.comm_bytes``.  One eval_shape probe, no devices."""
    _, payloads = probe_collective_shapes(cellprog, data, state,
                                          sizes=sizes, layout=layout)
    return wire_accounting(cellprog.schedule, payloads, sizes, compression)


def grid_bind_state(cellprog: CellProgram, data, state0, *, Pn: int, Qn: int,
                    compression=None, topology=None):
    """Engine-state plumbing shared by the grid-engine program builders.

    One build-time probe yields both the wire accounting and (when the
    policy carries error feedback) the zero EF residuals -- one
    ``(P, Q, *payload_shape)`` f32 buffer per stateful-codec collective,
    blocked layout, matching :func:`grid_program`'s ``ef`` operand.
    With a hierarchical ``topology`` the cross-pod codec's residuals
    join the same dict under ``"pod:"``-prefixed keys (sized by the
    payload aval: the intra-pod partial sum a cross-pod residual tracks
    has the per-cell payload shape) and the accounting is rewritten
    into intra/inter tiers.  Returns ``(full_state0, unwrap, acct)``
    where ``unwrap`` recovers the solver state from the full engine
    state (identity when no comm state is carried, so the uncompressed
    flat state layout is untouched)."""
    topo = _norm_topology(topology)
    sizes = {"data": Pn, "model": Qn}
    _, payloads = probe_collective_shapes(cellprog, data, state0,
                                          sizes=sizes, layout="blocked")
    acct = wire_accounting(cellprog.schedule, payloads, sizes, compression)
    acct = hierarchical_accounting(acct, topo, sizes)
    if compression is None and topo is None:
        return state0, (lambda s: s), acct
    ef0 = {}
    if compression is not None:
        ef0.update({
            name: jnp.zeros((Pn, Qn) + payloads[name].shape, jnp.float32)
            for name in compression.stateful_names(cellprog.schedule)})
    for name in hier_ef_names(cellprog.schedule, topo):
        ef0[_POD_EF + name] = jnp.zeros((Pn, Qn) + payloads[name].shape,
                                        jnp.float32)
    return (state0, ef0), (lambda s: s[0]), acct


def mesh_program(cellprog: CellProgram, mesh, data, state0, *,
                 data_axis="data", model_axis: str = "model",
                 staleness: int = 0, compression=None,
                 overlap: bool = False, topology=None):
    """Bind a CellProgram to a mesh: returns ``(step, comm0, acct)``
    where ``step(t, data, (state, comm_state))`` is jitted, ``comm0``
    holds the zero-initialized communication state (staleness rings
    under ``"stale"``, error-feedback residuals under ``"ef"``,
    cross-pod residuals under ``"hier_ef"``; ``{}`` when
    ``staleness == 0`` and no stateful codec runs, in which case the
    jaxpr is exactly the sync engine's), and ``acct`` is the program's
    exact per-step wire accounting (:func:`comm_accounting`, rewritten
    into intra/inter-pod tiers under a hierarchical ``topology``).

    ``overlap=True`` (the overlap engine) runs the cells under
    :class:`~repro.core.comm.OverlapComm` and **donates the full state**
    to the jitted step on accelerator backends, so the staleness rings
    are double-buffered reduction slots XLA can keep in flight across
    steps instead of defensively copying.  Donation is skipped on CPU
    (where it is a no-op) to keep host-side re-stepping from saved
    states unrestricted there; callers can check
    ``EngineProgram.donated``."""
    topo = _norm_topology(topology)
    daxes = as_axes(data_axis)
    sizes = {"data": axes_size(mesh, data_axis),
             "model": axes_size(mesh, model_axis)}
    policy = compression
    raw = mesh_step_fn(cellprog, mesh, data_axis=data_axis,
                       model_axis=model_axis, staleness=staleness,
                       compression=policy, overlap=overlap, topology=topo)
    results, payloads = probe_collective_shapes(cellprog, data, state0,
                                                sizes=sizes)
    acct = wire_accounting(cellprog.schedule, payloads, sizes, policy)
    acct = hierarchical_accounting(acct, topo, sizes)
    comm0 = {}
    dspec = daxes if len(daxes) > 1 else daxes[0]
    put = _putter(mesh)
    if staleness > 0:
        comm0["stale"] = {}
        for name, aval in results.items():
            shape = (sizes["data"], sizes["model"], staleness) + aval.shape
            comm0["stale"][name] = put(jnp.zeros(shape, aval.dtype),
                                       P(dspec, model_axis))
    ef_names = policy.stateful_names(cellprog.schedule) \
        if policy is not None else ()
    if ef_names:
        comm0["ef"] = {
            name: put(jnp.zeros((sizes["data"], sizes["model"])
                                + payloads[name].shape, jnp.float32),
                      P(dspec, model_axis))
            for name in ef_names}
    hnames = hier_ef_names(cellprog.schedule, topo)
    if hnames:
        comm0["hier_ef"] = {
            name: put(jnp.zeros((sizes["data"], sizes["model"])
                                + payloads[name].shape, jnp.float32),
                      P(dspec, model_axis))
            for name in hnames}

    def step_fn(t, data, full_state):
        state, cbufs = full_state
        return raw(t, data, state, cbufs)

    donate = bool(overlap) and staleness > 0 and overlap_donates()
    step = jax.jit(step_fn, donate_argnums=(2,)) if donate \
        else jax.jit(step_fn)
    return step, comm0, acct


def overlap_donates() -> bool:
    """Whether the overlap engine donates its state to the jitted step
    on this backend (donation is a no-op on CPU, and skipping it there
    keeps host-side re-stepping from saved states unrestricted)."""
    return jax.default_backend() != "cpu"


def mesh_local_step(cellprog: CellProgram, mesh, *, data_axis="data",
                    model_axis: str = "model"):
    """Jitted collective-free twin of a mesh program's step:
    ``local(t, data, state) -> state`` runs the same shard_map cell with
    every declared reduction executed cell-locally
    (:class:`~repro.core.comm.LocalComm`).  Numerically wrong on
    purpose: it is the program that leaves out its exchanges (the
    benchmark's ``exchange_left_out`` fault)."""
    raw = mesh_step_fn(cellprog, mesh, data_axis=data_axis,
                       model_axis=model_axis, comm_local=True)

    @jax.jit
    def local(t, data, state):
        out, _ = raw(t, data, state, {})
        return out

    return local


def prepare_shard_map(mesh, X, y, *, data_axis="data", model_axis="model",
                      m_multiple: int | None = None,
                      tracer=None) -> ShardMapData:
    """Pad (X, y) so the mesh divides both axes and place the shards.

    The padding rule is identical to ``partition(..., m_multiple=P*Q)``,
    so a shard_map cell sees the same (n_p, m_q) block as the simulated
    grid's cell (p, q).  The host's padding is a ``repro.prep.partition``
    span, the puts a ``repro.prep.transfer`` span, in ``tracer`` (default
    the profiler-only tracer)."""
    tr = as_tracer(tracer, PROFILER_TRACER)
    Pn = axes_size(mesh, data_axis)
    Qn = axes_size(mesh, model_axis)
    if m_multiple is not None and m_multiple % Qn:
        raise ValueError(f"m_multiple={m_multiple} not a multiple of Q={Qn}")
    with tr.span("repro.prep.partition"):
        n, m = X.shape
        n_pad = _ceil_to(n, Pn)
        m_pad = _ceil_to(m, m_multiple or Qn)
        Xp = np.zeros((n_pad, m_pad), np.float32)
        Xp[:n, :m] = np.asarray(X, np.float32)
        yp = np.zeros((n_pad,), np.float32)
        yp[:n] = np.asarray(y, np.float32)
        maskp = np.zeros((n_pad,), np.float32)
        maskp[:n] = 1.0
    daxes = as_axes(data_axis)
    put = _putter(mesh)
    with tr.span("repro.prep.transfer", bytes=host_nbytes(Xp, yp, maskp)):
        return ShardMapData(
            mesh=mesh,
            x=put(jnp.asarray(Xp), P(daxes, model_axis)),
            y=put(jnp.asarray(yp), P(daxes)),
            mask=put(jnp.asarray(maskp), P(daxes)),
            n=n, m=m, P=Pn, Q=Qn,
            data_axis=data_axis, model_axis=model_axis)
