"""Unified solver framework: one API over D3CA / RADiSA / SFK / ADMM.

The paper's three doubly distributed optimizers -- plus the stochastic
Fang--Klabjan scheme of the follow-up paper -- share one P x Q execution
story (the way CoCoA frames local solvers as pluggable subproblems and
SCOPE separates the outer cooperative loop from the local computation).
This module provides that story once:

  * a :class:`Solver` protocol with a registry --
    ``get_solver("d3ca" | "radisa" | "sfk" | "admm")`` returns the
    solver class;
  * orthogonal knobs threaded end-to-end:
      - ``engine="simulated" | "shard_map" | "async" | "overlap"`` --
        vmap grid on one device, one block per device on a
        (data=P, model=Q) mesh with synchronous reductions, the same
        mesh execution with bounded-staleness reductions, or the
        communication-overlap engine (async consumption contract plus
        donated in-flight reduction slots and selective host syncs so
        the local solve overlaps the wire; ``"sync"`` is accepted as an
        alias for ``"shard_map"``);
      - ``staleness=tau``  -- async/overlap engines: every collective
        the solver's CommSchedule declares is applied with delay tau
        (tau = 0 reproduces the sync engine bit for bit);
      - ``topology="pods=G[:codec]"``  -- hierarchical topology-aware
        reductions: full-precision psum within each of G pods,
        codec-compressed (with error feedback) across pods, on both
        the grid and mesh engines;
      - ``local_backend="ref" | "pallas"``    -- pure-jnp cell-local
        solver vs the Pallas TPU kernels (interpret mode on CPU), used
        inside the vmap grid and inside each shard_map cell alike;
      - ``block_format="dense" | "sparse"``   -- per-cell (n_p, m_q)
        dense tiles vs padded-ELL sparse cells whose memory scales with
        the nonzero count (news20-scale instances; accepts a
        :class:`~repro.data.sparse.CSRMatrix` without ever densifying);
      - ``compression=...``  -- a codec spec / CompressionPolicy mapping
        the solver's declared collectives to compression codecs
        (``"int8"``, ``"fp8"``, ``"topk:0.1"``, or per-collective
        ``"w_contrib=int8,dalpha=identity"``) with error feedback;
        ``None`` builds the exact uncompressed program, and the
        identity codec is bit-identical to it.  ``"adaptive..."``
        specs build a :class:`~repro.core.compress.CompressionSchedule`
        -- staged codec switching (top-k early, int8 near convergence)
        driven by the observed ``rel_opt`` slope, each stage a
        warm-started program rebuild.  Every program reports exact
        bytes-on-wire (``SolveResult.comm_bytes`` + cumulative
        ``comm_bytes`` per history entry);
  * a shared outer driver: objective / duality-gap history, early
    stopping, warm starts from a previous ``w`` / ``alpha``.

Example::

    from repro.core.solver import get_solver

    solver = get_solver("d3ca")(engine="async", staleness=2,
                                local_backend="pallas",
                                block_format="sparse")
    res = solver.solve("hinge", X, y, P=4, Q=2,
                       cfg=D3CAConfig(lam=1e-2, outer_iters=20),
                       f_star=f_star, tol=1e-2)
    res.w, res.history[-1]["objective"], res.converged

Engine x backend support matrix: see README ("Unified solver API").
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Type

from .admm import (ADMMConfig, admm_shard_map_program, admm_simulated_program,
                   make_admm_step)
from .comm_model import as_topology
from .compress import CompressionSchedule, as_compression
from .d3ca import (D3CAConfig, d3ca_shard_map_program, d3ca_simulated_program,
                   make_d3ca_step)
from .engines import (EngineProgram, drive, prepare_shard_map,
                      prepare_shard_map_sparse)
from .losses import get_loss
from .partition import partition, partition_sparse
from .radisa import (RADiSAConfig, make_radisa_step,
                     radisa_shard_map_program, radisa_simulated_program)
from .reference import rel_opt
from .sfk import (SFKConfig, make_sfk_step, sfk_shard_map_program,
                  sfk_simulated_program)
from .util import axes_size, host_nbytes

ENGINES = ("simulated", "shard_map", "async", "overlap")
#: "sync" names today's synchronous mesh policy explicitly (the
#: CommSchedule terminology); it is the same engine as "shard_map".
ENGINE_ALIASES = {"sync": "shard_map"}
LOCAL_BACKENDS = ("ref", "pallas")
BLOCK_FORMATS = ("dense", "sparse")


@dataclasses.dataclass
class SolveResult:
    """Outcome of :meth:`Solver.solve`."""

    w: Any                          # (m,) global primal iterate
    alpha: Optional[Any]            # (n,) global dual iterate (D3CA only)
    history: List[Dict[str, float]]  # per-iter: iter, time_s, objective,
    #                                  [duality_gap], [rel_opt]; timed
    #                                  solves (tracer=/registry=) add
    #                                  step_s, host_s
    iters: int                      # outer iterations actually run
    converged: bool                 # True iff early stopping triggered
    solver: str
    engine: str
    local_backend: str
    block_format: str = "dense"
    staleness: int = 0
    compression: Optional[str] = None   # canonical policy/schedule spec
    topology: Optional[str] = None      # canonical topology spec, or None
    #: exact per-step wire accounting of the declared collectives (see
    #: repro.core.compress.wire_accounting); history entries carry the
    #: cumulative "comm_bytes" derived from it
    comm_bytes: Optional[Dict] = None


def _unpack_warm_start(warm_start):
    if warm_start is None:
        return None, None
    if isinstance(warm_start, SolveResult):
        return warm_start.w, warm_start.alpha
    if isinstance(warm_start, (tuple, list)):
        w0 = warm_start[0] if len(warm_start) > 0 else None
        alpha0 = warm_start[1] if len(warm_start) > 1 else None
        return w0, alpha0
    return warm_start, None         # bare w


class Solver:
    """Base class: one doubly distributed optimizer under two engines.

    Subclasses bind the algorithm (config class + the two
    ``EngineProgram`` builders); everything about *running* a solve --
    data prep and padding, the outer loop, history, early stopping, warm
    starts -- lives here, once.
    """

    name: str = ""
    config_cls: Type = None
    has_dual: bool = False
    #: ADMM's inner solve is a cached Cholesky; it accepts the knob but
    #: has no kernel to dispatch to.
    uses_local_backend: bool = True
    #: True when the solver's cell program accepts a per-row activity
    #: gate (the incremental online-update path; D3CA only).
    supports_row_gate: bool = False

    def __init__(self, engine: str = "simulated", local_backend: str = "ref",
                 block_format: str = "dense", staleness: int = 0,
                 compression=None, topology=None,
                 program_cache: bool = False):
        engine = ENGINE_ALIASES.get(engine, engine)
        if engine not in ENGINES:
            raise ValueError(f"engine={engine!r}; expected one of {ENGINES}")
        if local_backend not in LOCAL_BACKENDS:
            raise ValueError(f"local_backend={local_backend!r}; expected one "
                             f"of {LOCAL_BACKENDS}")
        if block_format not in BLOCK_FORMATS:
            raise ValueError(f"block_format={block_format!r}; expected one "
                             f"of {BLOCK_FORMATS}")
        staleness = int(staleness)
        if staleness < 0:
            raise ValueError(f"staleness={staleness} must be >= 0 (the "
                             "reduction delay tau of the async/overlap "
                             "engines)")
        if staleness > 0 and engine not in ("async", "overlap"):
            raise ValueError(
                f"staleness={staleness} needs engine='async' or "
                f"engine='overlap'; the {engine!r} engine applies every "
                "reduction synchronously.  Pass engine='async' or "
                "engine='overlap' (staleness=0 on either reproduces "
                "'shard_map' exactly).")
        self.engine = engine
        self.local_backend = local_backend
        self.block_format = block_format
        self.staleness = staleness
        #: normalized CompressionPolicy or CompressionSchedule (None =
        #: no compression machinery at all -- the engines build the
        #: exact uncompressed program).  Validated against the solver's
        #: declared CommSchedule when the program is built.
        self.compression = as_compression(compression)
        #: hierarchical reduction topology (None = flat reductions)
        self.topology = as_topology(topology)
        #: current CompressionSchedule stage (policies are per-stage)
        self._stage = 0
        #: reuse jitted step callables across repeated program builds
        #: with constant shapes (always on inside :meth:`update`, where
        #: shapes are constant by design).  Keyed on (solver, engine,
        #: loss, cfg-minus-outer_iters, backend, format, gate-ness,
        #: shapes, grid); bypassed under compression / topology /
        #: staleness / overlap, whose programs carry per-build device
        #: state (EF residuals, rings, donated buffers).
        self.program_cache = bool(program_cache)
        self._prog_cache: Dict = {}

    @property
    def compression_spec(self) -> Optional[str]:
        return self.compression.spec if self.compression is not None else None

    @property
    def active_policy(self):
        """The CompressionPolicy the *current* program runs under: the
        schedule's current stage, or the fixed policy, or None."""
        if isinstance(self.compression, CompressionSchedule):
            return self.compression.stages[self._stage]
        return self.compression

    @property
    def topology_spec(self) -> Optional[str]:
        return self.topology.spec if self.topology is not None else None

    # ---- subclass hooks ---------------------------------------------------
    def _simulated_program(self, loss, data, cfg, w0, alpha0,
                           cache=None) -> EngineProgram:
        raise NotImplementedError

    def _shard_map_program(self, loss, sdata, cfg, w0, alpha0,
                           staleness: int = 0, cache=None) -> EngineProgram:
        raise NotImplementedError

    def _build_cache(self, loss_name, cfg, X, P, Q, mesh, gated: bool):
        """The per-key dict the program builders memoize their jitted
        steps in, or None when caching is off / unsafe (compression,
        topology, staleness and overlap programs carry per-build device
        state -- EF residuals, staleness rings, donated ring slots)."""
        if not self.program_cache:
            return None
        if (self.active_policy is not None or self.topology is not None
                or self.staleness > 0 or self.engine == "overlap"):
            return None
        key = (self.name, self.engine, loss_name,
               dataclasses.replace(cfg, outer_iters=0),
               self.local_backend, self.block_format, gated,
               tuple(X.shape), P, Q, mesh)
        return self._prog_cache.setdefault(key, {})

    # ---- program construction --------------------------------------------
    def program(self, loss_name: str, X, y, *, P: int = None, Q: int = None,
                cfg=None, mesh=None, warm_start=None,
                data_axis="data", model_axis: str = "model",
                row_gate=None, tracer=None) -> EngineProgram:
        """Bind the solver to data under the configured engine/backend.

        Pads the feature dimension to a multiple of P*Q (identically for
        both engines and both block formats) so RADiSA's P sub-blocks
        always divide m_q and the engines see bit-identical blocks.
        ``block_format="sparse"`` accepts a
        :class:`~repro.data.sparse.CSRMatrix` ``X`` and never
        materializes the dense matrix; dense ``X`` is converted cell by
        cell.  ``block_format="dense"`` densifies a CSR input.

        Args:
          loss_name: a key of :data:`repro.core.losses.LOSSES`.
          X, y: the (n, m) training matrix and (n,) labels.
          P, Q: observation/feature partition counts (required unless a
            ``mesh`` carrying both axes is given).
          cfg: the solver's config dataclass (``config_cls()`` default).
          mesh: an explicit jax mesh for the mesh engines.
          warm_start: a :class:`SolveResult`, a ``(w, alpha)`` tuple, or
            a bare ``w`` to initialize the iterates from.
          data_axis, model_axis: mesh axis names.
          row_gate: optional (n,) 0/1 per-row activity gate restricting
            dual updates to gated-on rows -- the incremental
            online-update path.  Only solvers with
            ``supports_row_gate`` accept it.
          tracer: a :class:`repro.obs.Tracer` (default the profiler-only
            tracer) taking the ``repro.prep.partition`` (cutting the
            blocks, with the ELL counters), ``repro.prep.transfer``
            (each put of host arrays, with their ``bytes``) and
            ``repro.prep.bind`` (building the program, with its
            program-cache ``cache`` hit / miss / off) spans.

        Returns:
          An :class:`EngineProgram` ready for :func:`engines.drive`.  On
          the simulated engine with dense blocks it carries ``primal_of``
          / ``dual_of``, the objectives on those blocks.

        Raises:
          ValueError: on a missing grid spec, a mesh/grid mismatch, an
            unsupported ``row_gate``, or a topology that does not
            divide P.
        """
        from repro.obs.trace import PROFILER_TRACER, as_tracer
        tr = as_tracer(tracer, PROFILER_TRACER)
        loss = get_loss(loss_name)
        cfg = cfg if cfg is not None else self.config_cls()
        if row_gate is not None and not self.supports_row_gate:
            raise ValueError(
                f"solver {self.name!r} has no incremental row-gate path; "
                "gated warm-started passes are a dual-solver feature "
                "(use 'd3ca')")
        cache = self._build_cache(loss_name, cfg, X, P, Q, mesh,
                                  row_gate is not None)
        w0, alpha0 = _unpack_warm_start(warm_start)
        starts = (w0, alpha0, row_gate)
        if self.engine == "simulated" and host_nbytes(*starts):
            # the grid builders take the starting iterates (and the gate)
            # as device arrays; the mesh engines pad and place host ones
            # themselves, inside their bind
            import jax
            import jax.numpy as jnp
            with tr.span("repro.prep.transfer", bytes=host_nbytes(*starts)):
                w0, alpha0, row_gate = (
                    a if a is None or isinstance(a, jax.Array)
                    else jnp.asarray(a) for a in starts)
        gate_kw = {} if row_gate is None else {"row_gate": row_gate}
        hit = "off" if cache is None else "hit" if cache else "miss"
        sparse = self.block_format == "sparse"
        topo = self.topology
        pods = topo.pods if topo is not None else 1
        if not sparse and hasattr(X, "toarray"):
            with tr.span("repro.prep.partition"):
                X = X.toarray()   # CSR input under block_format="dense"
        if self.engine == "simulated":
            if P is None or Q is None:
                raise ValueError("engine='simulated' needs P and Q")
            if pods > 1 and P % pods:
                raise ValueError(f"topology pods={pods} must divide P={P}")
            cut = partition_sparse if sparse else partition
            data = cut(X, y, P, Q, m_multiple=P * Q, tracer=tr)
            with tr.span("repro.prep.bind", cache=hit):
                prog = self._simulated_program(loss, data, cfg, w0, alpha0,
                                               cache=cache, **gate_kw)
                if sparse:
                    # the caller's CSR X keeps its entries on the device;
                    # the ELL blocks would pad them (more than twice as
                    # many slots on the real-sim shape)
                    return prog
                return dataclasses.replace(
                    prog,
                    primal_of=partial(data.objective, loss, lam=cfg.lam),
                    dual_of=(partial(data.dual_objective, loss, lam=cfg.lam)
                             if prog.alpha_of else None))
        if mesh is None:
            if P is None or Q is None:
                raise ValueError(f"engine={self.engine!r} needs a mesh "
                                 "or P and Q")
            from repro.launch.mesh import make_grid_mesh, make_mesh
            if pods > 1:
                # hierarchical reductions want the pod split as a real
                # mesh axis: (pod=G, data=P/G, model=Q)
                if P % pods:
                    raise ValueError(f"topology pods={pods} must divide "
                                     f"P={P}")
                mesh = make_mesh((pods, P // pods, Q),
                                 ("pod", "data", "model"))
                data_axis = ("pod", "data")
            else:
                mesh = make_grid_mesh(P, Q)
        elif pods > 1 and data_axis == "data" and "pod" in mesh.axis_names:
            data_axis = ("pod", "data")   # pod-split mesh supplied directly
        Pn = axes_size(mesh, data_axis)
        Qn = axes_size(mesh, model_axis)
        if (P is not None and P != Pn) or (Q is not None and Q != Qn):
            raise ValueError(f"mesh is {Pn}x{Qn} but P={P}, Q={Q} requested")
        prep = prepare_shard_map_sparse if sparse else prepare_shard_map
        sdata = prep(mesh, X, y, data_axis=data_axis,
                     model_axis=model_axis, m_multiple=Pn * Qn, tracer=tr)
        mesh_shape = "x".join(str(s) for s in mesh.devices.shape)
        with tr.span("repro.prep.bind", cache=hit, mesh=mesh_shape):
            return self._shard_map_program(loss, sdata, cfg, w0, alpha0,
                                           staleness=self.staleness,
                                           cache=cache, **gate_kw)

    # ---- the shared outer driver ------------------------------------------
    def solve(self, loss_name: str, X, y, *, P: int = None, Q: int = None,
              cfg=None, mesh=None, warm_start=None,
              tol: Optional[float] = None, f_star: Optional[float] = None,
              record_history: bool = True,
              callback: Optional[Callable] = None,
              tracer=None, registry=None, monitor=None,
              row_gate=None) -> SolveResult:
        """Run the solver.

        Early stopping (when ``tol`` is given) uses, in order of
        preference: relative optimality vs ``f_star``; the duality gap
        (dual solvers); the relative objective change between iterates.
        ``callback(t, w, alpha)`` fires every iteration.

        Under an adaptive :class:`CompressionSchedule` the solve runs as
        a sequence of warm-started stages -- one program build per codec
        stage, advanced when the convergence metric's log10 slope
        flattens below the schedule's ``slope_tol`` -- and the merged
        history tags every entry with ``stage`` and ``codec``.

        Args:
          loss_name, X, y, P, Q, cfg, mesh, warm_start, row_gate: see
            :meth:`program`.
          tol: early-stopping tolerance (None disables early stopping).
          f_star: reference optimum enabling the ``rel_opt`` history
            field and rel-opt early stopping.
          record_history: collect per-iteration history entries.
          callback: ``callback(t, w, alpha)`` per outer iteration.
          tracer: a :class:`repro.obs.Tracer` (enables the timed path;
            default the profiler-only tracer, which adds no sync).
          registry: a :class:`repro.obs.Registry` for per-iter metrics
            (enables the timed path).
          monitor: a :class:`repro.obs.HealthMonitor`; polled once per
            outer iteration (rules read the registry only -- iterates
            are untouched).

        Returns:
          A :class:`SolveResult`.

        Raises:
          ValueError: propagated from :meth:`program` (bad grid spec,
            unsupported ``row_gate``, ...).
        """
        cfg = cfg if cfg is not None else self.config_cls()
        sched = (self.compression
                 if isinstance(self.compression, CompressionSchedule)
                 else None)
        if sched is None:
            res, _ = self._solve_stage(
                loss_name, X, y, P=P, Q=Q, cfg=cfg, mesh=mesh,
                warm_start=warm_start, tol=tol, f_star=f_star,
                record_history=record_history, callback=callback,
                tracer=tracer, registry=registry, monitor=monitor,
                row_gate=row_gate)
            return res
        history: List[Dict[str, float]] = []
        warm = warm_start
        iters_done = 0
        time_off, bytes_off = 0.0, 0
        res = None
        try:
            for si in range(len(sched.stages)):
                remaining = cfg.outer_iters - iters_done
                if remaining <= 0:
                    break
                self._stage = si
                last = si == len(sched.stages) - 1
                stage_cfg = dataclasses.replace(cfg, outer_iters=remaining)
                res, advanced = self._solve_stage(
                    loss_name, X, y, P=P, Q=Q, cfg=stage_cfg, mesh=mesh,
                    warm_start=warm, tol=tol, f_star=f_star,
                    record_history=record_history, callback=callback,
                    tracer=tracer, registry=registry, monitor=monitor,
                    row_gate=row_gate,
                    advance=None if last else sched,
                    iter_offset=iters_done, time_offset=time_off,
                    bytes_offset=bytes_off, stage=si)
                history.extend(res.history)
                iters_done += res.iters
                if res.history:
                    time_off = res.history[-1]["time_s"]
                    bytes_off = res.history[-1].get("comm_bytes", bytes_off)
                warm = res
                if res.converged or not advanced:
                    break
        finally:
            self._stage = 0
        return dataclasses.replace(res, history=history, iters=iters_done,
                                   compression=sched.spec)

    def update(self, loss_name: str, X, y, *, touched, warm_start,
               P: int = None, Q: int = None, cfg=None, mesh=None,
               passes: int = 1, tracer=None, registry=None, monitor=None,
               record_history: bool = True) -> SolveResult:
        """Incremental-update entry point for the online service.

        Runs ``passes`` warm-started outer iterations in which dual
        updates are restricted to the ``touched`` rows (the cells whose
        row partition received new observations); every other row's
        alpha is frozen, but the primal-dual map still sums the full
        dual, so the returned ``w`` is exact for the whole buffer.

        The compiled-program cache is always on here: the observation
        buffer has a constant shape by design, so every update after the
        first reuses the previously traced+compiled step instead of
        paying the ~seconds of per-update program rebuild.

        Args:
          loss_name, X, y, P, Q, cfg, mesh: see :meth:`solve`.  ``X``
            is the full observation buffer (constant shape across
            updates keeps the jit cache warm).
          touched: integer row indices that may move their dual.
          warm_start: the previous iterates (required -- an incremental
            update without a warm start is just a truncated cold
            solve).
          passes: warm-started outer iterations over the touched cells.
          tracer, registry: see :meth:`solve`.

        Returns:
          A :class:`SolveResult` whose ``w``/``alpha`` fold the new
          observations into the previous model.

        Raises:
          ValueError: when this solver has no row-gate path
            (``supports_row_gate`` is False) or ``warm_start`` is None.
        """
        if warm_start is None:
            raise ValueError("incremental update needs warm_start=(w, "
                             "alpha); for a cold model run solve()")
        import numpy as np
        gate = np.zeros((X.shape[0],), dtype=np.float32)
        gate[np.asarray(touched, dtype=np.int64)] = 1.0
        cfg = cfg if cfg is not None else self.config_cls()
        cfg = dataclasses.replace(cfg, outer_iters=int(passes))
        prev_cache = self.program_cache
        self.program_cache = True
        try:
            return self.solve(loss_name, X, y, P=P, Q=Q, cfg=cfg, mesh=mesh,
                              warm_start=warm_start, row_gate=gate,
                              tracer=tracer, registry=registry,
                              monitor=monitor,
                              record_history=record_history)
        finally:
            self.program_cache = prev_cache

    def _solve_stage(self, loss_name: str, X, y, *, P: int = None,
                     Q: int = None, cfg=None, mesh=None, warm_start=None,
                     tol: Optional[float] = None,
                     f_star: Optional[float] = None,
                     record_history: bool = True,
                     callback: Optional[Callable] = None,
                     tracer=None, registry=None, monitor=None,
                     row_gate=None,
                     advance=None, iter_offset: int = 0,
                     time_offset: float = 0.0, bytes_offset: int = 0,
                     stage: Optional[int] = None):
        """One program build + outer loop.  Returns ``(result,
        advanced)`` where ``advanced`` reports an adaptive-schedule
        stage switch (``advance.should_advance`` fired on the observed
        convergence metric; the result is then a warm-start point, not
        a converged solve).

        Spans: the solve is a ``repro.solve`` span holding ``repro.prep``
        (:meth:`program`'s ``repro.prep.partition`` /
        ``repro.prep.transfer`` / ``repro.prep.bind``), one ``repro.iter``
        per outer iteration (:func:`~repro.core.engines.drive`'s
        ``repro.step`` and ``repro.observe``; the latter holds
        ``repro.observe.primal`` and ``repro.observe.dual``, one per
        objective evaluation, with its ``operands``: ``"blocks"`` on the
        program's device blocks (the simulated dense grid), ``"host"`` on
        the caller's X, and the ``h2d_bytes`` of the host-resident
        operands it hands to JAX) and ``repro.result``.  Without a
        ``tracer`` they go to the JAX profiler only, at no sync and no
        clock read.

        Telemetry (both default off; the untimed path is the exact
        legacy loop, bit-identical results):

          * ``tracer`` -- a :class:`repro.obs.Tracer` keeping the same
            spans as events (Chrome trace / JSONL) as well;
          * ``registry`` -- a :class:`repro.obs.Registry`.  Per-iter
            metrics (``solver/objective``, ``solver/step_s``,
            ``solver/host_s``, cumulative ``solver/comm_bytes``,
            per-collective ``compress/ef_norm/*`` when error feedback is
            active, ``async/ring_occupancy`` under staleness) land in it,
            keyed by ``{solver=..., engine=...}`` labels.

        Either one switches the driver to its timed path, which adds a
        per-step device sync and per-iter ``step_s`` / ``host_s`` fields
        to the history; the iterates themselves are unchanged.  Where a
        step's device time goes (kernel, collectives) is read from the
        profile, under the ``repro.*`` spans and ``repro.comm.<name>``
        scopes.
        """
        from repro.obs.trace import PROFILER_TRACER, as_tracer
        tr = as_tracer(tracer, PROFILER_TRACER)
        reg = registry
        timed = tr.enabled or reg is not None
        loss = get_loss(loss_name)
        cfg = cfg if cfg is not None else self.config_cls()
        policy = self.active_policy
        labels = {"solver": self.name, "engine": self.engine}
        with tr.span("repro.solve", **labels):
            with tr.span("repro.prep"):
                prog = self.program(loss_name, X, y, P=P, Q=Q, cfg=cfg,
                                    mesh=mesh, warm_start=warm_start,
                                    row_gate=row_gate, tracer=tr)
            lam = cfg.lam
            history: List[Dict[str, float]] = []
            need_obs = (record_history or callback is not None
                        or tol is not None or advance is not None)
            prev_f = [None]
            advanced = [False]
            metric_vals: List[float] = []
            bytes_per_step = (prog.comm_bytes or {}).get("bytes_per_step")
            t0 = time.perf_counter()
            last_step: Dict[str, float] = {}

            def on_step(t, step_s):
                last_step["step_s"] = step_s
                if reg is not None:
                    reg.histogram("solver/step_s", **labels).observe(step_s)
                    if bytes_per_step is not None:
                        reg.counter("solver/comm_bytes", **labels).inc(
                            bytes_per_step)

            # the objectives on the program's device blocks where it has
            # them, else on the caller's (host or CSR) X
            if prog.primal_of is not None:
                operands, held = "blocks", ()
                primal, dual = prog.primal_of, prog.dual_of
            else:
                operands, held = "host", (X, y)
                primal = partial(loss.objective, X, y, lam=lam)
                dual = partial(loss.dual_objective, X, y, lam=lam)

            def observe(t, state):
                if not need_obs:
                    return False
                th0 = time.perf_counter()
                w = prog.w_of(state)
                alpha = prog.alpha_of(state) if prog.alpha_of else None
                with tr.span("repro.observe.primal", iter=t,
                             operands=operands,
                             h2d_bytes=host_nbytes(*held, w)):
                    f = float(primal(w))
                entry = {"iter": t + iter_offset,
                         "time_s": time.perf_counter() - t0 + time_offset,
                         "objective": f}
                if stage is not None:
                    entry["stage"] = stage
                    entry["codec"] = policy.spec if policy is not None \
                        else None
                if timed:
                    entry.update(last_step)
                if bytes_per_step is not None:
                    # cumulative bytes-on-wire after t outer steps (every
                    # declared collective launches once per step)
                    entry["comm_bytes"] = bytes_offset + bytes_per_step * t
                if alpha is not None:
                    with tr.span("repro.observe.dual", iter=t,
                                 operands=operands,
                                 h2d_bytes=host_nbytes(*held, alpha)):
                        entry["duality_gap"] = float(f - dual(alpha))
                if f_star is not None:
                    entry["rel_opt"] = float(rel_opt(f, f_star))
                if timed:
                    # objective / gap / rel_opt eval is the host phase
                    entry["host_s"] = time.perf_counter() - th0
                if reg is not None:
                    reg.counter("solver/iters", **labels).inc()
                    reg.gauge("solver/objective", **labels).set(
                        entry["objective"])
                    if "duality_gap" in entry:
                        reg.gauge("solver/duality_gap", **labels).set(
                            entry["duality_gap"])
                    if "rel_opt" in entry:
                        reg.gauge("solver/rel_opt", **labels).set(
                            entry["rel_opt"])
                    if "host_s" in entry:
                        reg.histogram("solver/host_s", **labels).observe(
                            entry["host_s"])
                    if prog.ef_of is not None:
                        import numpy as np
                        for cname, buf in prog.ef_of(state).items():
                            reg.gauge(f"compress/ef_norm/{cname}",
                                      **labels).set(
                                float(np.linalg.norm(np.asarray(buf))))
                    if self.staleness > 0:
                        # filled FIFO slots / ring capacity (the rings
                        # are seeded full at t=1; before that they hold
                        # the first reduction, so occupancy ramps once)
                        reg.gauge("async/ring_occupancy", **labels).set(
                            min(t, self.staleness) / self.staleness)
                if record_history:
                    history.append(entry)
                if callback is not None:
                    callback(t + iter_offset, w, alpha)
                stop = False
                if tol is not None:
                    if f_star is not None:
                        stop = entry["rel_opt"] < tol
                    elif "duality_gap" in entry:
                        stop = entry["duality_gap"] < tol
                    elif prev_f[0] is not None:
                        stop = abs(f - prev_f[0]) <= tol * max(1.0, abs(f))
                prev_f[0] = f
                if advance is not None and not stop:
                    metric_vals.append(entry.get("rel_opt", f))
                    if advance.should_advance(metric_vals):
                        advanced[0] = True
                        stop = True
                return stop

            state, iters, stopped = drive(
                prog, cfg.outer_iters, observe, tracer=tr,
                on_step=on_step if timed else None,
                monitor=monitor)
            with tr.span("repro.result"):
                res = SolveResult(
                    w=prog.w_of(state),
                    alpha=prog.alpha_of(state) if prog.alpha_of else None,
                    history=history, iters=iters,
                    converged=stopped and not advanced[0],
                    solver=self.name, engine=self.engine,
                    local_backend=self.local_backend,
                    block_format=self.block_format,
                    staleness=self.staleness,
                    compression=policy.spec if policy is not None else None,
                    topology=self.topology_spec,
                    comm_bytes=prog.comm_bytes)
            return res, advanced[0]


# ---------------------------------------------------------------------------
# the four solvers
# ---------------------------------------------------------------------------

_REGISTRY: Dict[str, Type[Solver]] = {}


def register_solver(cls: Type[Solver]) -> Type[Solver]:
    """Class decorator adding a :class:`Solver` subclass to the registry
    under its ``name`` attribute.  Returns the class unchanged, so it
    stacks with other decorators."""
    _REGISTRY[cls.name] = cls
    return cls


def get_solver(name: str) -> Type[Solver]:
    """Look up a solver class by name; instantiate with
    ``get_solver(name)(engine=..., local_backend=...)``.

    Raises:
      KeyError: for an unregistered name (the message lists what IS
        registered).
    """
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(f"unknown solver {name!r}; available: "
                       f"{available_solvers()}") from None


def available_solvers():
    """Sorted names of every registered solver
    (``["admm", "d3ca", "radisa", "sfk"]``)."""
    return sorted(_REGISTRY)


@register_solver
class D3CASolver(Solver):
    name = "d3ca"
    config_cls = D3CAConfig
    has_dual = True
    supports_row_gate = True                   # incremental online updates
    make_step = staticmethod(make_d3ca_step)   # for dry-run lowering

    def _simulated_program(self, loss, data, cfg, w0, alpha0,
                           row_gate=None, cache=None):
        return d3ca_simulated_program(loss, data, cfg,
                                      local_backend=self.local_backend,
                                      w0=w0, alpha0=alpha0,
                                      compression=self.active_policy,
                                      topology=self.topology,
                                      row_gate=row_gate, cache=cache)

    def _shard_map_program(self, loss, sdata, cfg, w0, alpha0,
                           staleness: int = 0, row_gate=None, cache=None):
        return d3ca_shard_map_program(loss, sdata, cfg,
                                      local_backend=self.local_backend,
                                      w0=w0, alpha0=alpha0,
                                      staleness=staleness,
                                      compression=self.active_policy,
                                      overlap=self.engine == "overlap",
                                      topology=self.topology,
                                      row_gate=row_gate, cache=cache)


@register_solver
class RADiSASolver(Solver):
    name = "radisa"
    config_cls = RADiSAConfig
    make_step = staticmethod(make_radisa_step)

    def _simulated_program(self, loss, data, cfg, w0, alpha0, cache=None):
        return radisa_simulated_program(loss, data, cfg,
                                        local_backend=self.local_backend,
                                        w0=w0,
                                        compression=self.active_policy,
                                        topology=self.topology,
                                        cache=cache)

    def _shard_map_program(self, loss, sdata, cfg, w0, alpha0,
                           staleness: int = 0, cache=None):
        return radisa_shard_map_program(loss, sdata, cfg,
                                        local_backend=self.local_backend,
                                        w0=w0, staleness=staleness,
                                        compression=self.active_policy,
                                        overlap=self.engine == "overlap",
                                        topology=self.topology,
                                        cache=cache)


@register_solver
class SFKSolver(Solver):
    """Stochastic Fang--Klabjan sampling scheme (arXiv 1803.11287): a
    primal solver whose outer iteration subsamples the observations --
    minibatch anchor gradients plus variance-reduced local steps on the
    sampled rows only (see :mod:`repro.core.sfk`)."""
    name = "sfk"
    config_cls = SFKConfig
    make_step = staticmethod(make_sfk_step)

    def _simulated_program(self, loss, data, cfg, w0, alpha0, cache=None):
        return sfk_simulated_program(loss, data, cfg,
                                     local_backend=self.local_backend,
                                     w0=w0,
                                     compression=self.active_policy,
                                     topology=self.topology, cache=cache)

    def _shard_map_program(self, loss, sdata, cfg, w0, alpha0,
                           staleness: int = 0, cache=None):
        return sfk_shard_map_program(loss, sdata, cfg,
                                     local_backend=self.local_backend,
                                     w0=w0, staleness=staleness,
                                     compression=self.active_policy,
                                     overlap=self.engine == "overlap",
                                     topology=self.topology, cache=cache)


@register_solver
class ADMMSolver(Solver):
    name = "admm"
    config_cls = ADMMConfig
    uses_local_backend = False     # knob accepted, inner solve is Cholesky
    make_step = staticmethod(make_admm_step)

    def _simulated_program(self, loss, data, cfg, w0, alpha0, cache=None):
        return admm_simulated_program(loss, data, cfg, w0=w0,
                                      compression=self.active_policy,
                                      topology=self.topology, cache=cache)

    def _shard_map_program(self, loss, sdata, cfg, w0, alpha0,
                           staleness: int = 0, cache=None):
        return admm_shard_map_program(loss, sdata, cfg, w0=w0,
                                      staleness=staleness,
                                      compression=self.active_policy,
                                      overlap=self.engine == "overlap",
                                      topology=self.topology, cache=cache)
