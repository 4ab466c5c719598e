"""D3CA -- Doubly Distributed Dual Coordinate Ascent (Algorithm 1).

The cell-local solver is ``local.local_sdca`` (pure jnp or the Pallas
SDCA kernel, selected by ``local_backend``).  Since Engine API v2 the
algorithm contributes ONE :class:`~repro.core.engines.CellProgram` --
the per-cell step math plus a CommSchedule declaring its two
reductions::

    CommSchedule().pmean("dalpha", axis="model")   # step 6 dual average
                  .psum("w_contrib", axis="data")  # step 9 primal-dual map

The generic executors in ``repro.core.engines`` run that single program
under every engine:

  * ``d3ca_simulated_program``  -- named-vmap grid on one device;
  * ``d3ca_shard_map_program``  -- a ``shard_map`` step over a
    (data=P, model=Q) mesh; ``staleness=tau`` turns the same program
    into the bounded-staleness async engine (tau = 0 is bit-identical
    to the sync path).

``d3ca_simulated`` / ``d3ca_distributed`` are thin compatibility
wrappers; the outer loop lives once in ``engines.drive`` /
``solver.Solver.solve``.  The engines are tested to agree to float
tolerance (tests/test_distributed.py, tests/test_solver.py).
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from .comm import CommSchedule
from .engines import (CellProgram, EngineProgram, SparseShardMapData,
                      cached_build, drive_with_callback, grid_bind_state,
                      grid_program, mesh_local_step, mesh_program,
                      mesh_step_fn, overlap_donates)
from .local import local_sdca, local_sdca_sparse
from .losses import Loss, get_loss
from .partition import (DoublyPartitioned, SparseDoublyPartitioned,
                        ell_scatter_add)


@dataclasses.dataclass(frozen=True)
class D3CAConfig:
    lam: float = 1e-2
    local_steps: Optional[int] = None   # H; default = one local epoch (n_p)
    step_mode: str = "exact"            # "exact" | "beta" (paper's lam/t)
    outer_iters: int = 20
    seed: int = 0


def d3ca_schedule() -> CommSchedule:
    """D3CA's two reduction points, as named in the paper."""
    return (CommSchedule()
            .pmean("dalpha", axis="model")
            .psum("w_contrib", axis="data"))


def d3ca_cell_program(loss: Loss, cfg: D3CAConfig, *, n: int, n_p: int,
                      m_q: Optional[int] = None, sparse: bool = False,
                      local_backend: str = "ref",
                      gated: bool = False,
                      per_problem: bool = False) -> CellProgram:
    """The ONE D3CA program every engine executes.

    Per-cell data: ``(key0, x_b[, vals_b], y_b, mask_b[, gate_b])`` -- an
    (n_p, m_q) dense block or an (n_p, k) padded-ELL cols/vals pair.
    Per-cell state: ``(alpha_b (n_p,), w_b (m_q,))``.

    ``gated=True`` appends a per-row activity gate ``gate_b (n_p,)`` to
    the data tuple: the local SDCA epoch masks its coordinate updates by
    ``mask_b * gate_b``, so rows gated off never move their dual, while
    the step-9 primal-dual map still sums EVERY row's alpha (the model
    stays exact for the whole dataset).  A gate of all ones is
    bit-identical to the ungated program.  This is the incremental
    online-update path: warm-started passes touch only the cells whose
    row partition received new observations.

    ``per_problem=True`` appends runtime scalars ``(lam_v, n_v)`` to the
    data tuple and uses them in place of ``cfg.lam`` / ``n`` everywhere;
    this is the fleet path, where the tenant vmap feeds each tenant its
    own regularizer and sample count through the same traced program.
    """
    lam = cfg.lam
    steps = cfg.local_steps or n_p
    if sparse and m_q is None:
        raise ValueError("sparse D3CA cells need m_q for the scatter-add")

    def cell(comm, t, data, state):
        if per_problem:
            *data, lam_t, n_t = data
        else:
            lam_t, n_t = lam, n
        if sparse:
            key0, cols_b, vals_b, y_b, mask_b, *rest = data
            x_parts = (cols_b, vals_b)
            local = local_sdca_sparse
        else:
            key0, x_b, y_b, mask_b, *rest = data
            x_parts = (x_b,)
            local = local_sdca
        step_mask = mask_b * rest[0] if gated else mask_b
        a_b, w_b = state
        Pn = comm.axis_size("data")
        Qn = comm.axis_size("model")
        beta = lam_t / t
        key_t = jax.random.fold_in(key0, t)
        p = comm.axis_index("data")
        key_p = jax.random.fold_in(key_t, p)   # coordinate order per p
        dalpha = local(loss, *x_parts, y_b, step_mask, a_b, w_b,
                       lam=lam_t, n=n_t, Q=Qn, steps=steps, key=key_p,
                       step_mode=cfg.step_mode, beta=beta,
                       backend=local_backend)
        # step 6: alpha_[p,.] += (1/P) mean_q dalpha[p, q]
        a_new = a_b + comm("dalpha", dalpha) / Pn
        # step 9: w_[., q] = (1/(lam n)) sum_p alpha_[p,q]^T x_[p,q]
        with jax.named_scope("repro.d3ca.map"):
            am = a_new * mask_b
            contrib = (ell_scatter_add(m_q, cols_b, vals_b, am) if sparse
                       else am @ x_b)
            w_new = comm("w_contrib", contrib) / (lam_t * n_t)
        return a_new, w_new

    x_specs = ((("data", "model"), ("data", "model")) if sparse
               else (("data", "model"),))
    gate_specs = ((("data",),) if gated else ())
    pp_specs = (((), ()) if per_problem else ())
    data_specs = ((),) + x_specs + (("data",), ("data",)) + gate_specs \
        + pp_specs
    state_specs = (("data",), ("model",))
    return CellProgram(d3ca_schedule(), cell, data_specs, state_specs)


# ----------------------------------------------------------------------------
# simulated grid engine
# ----------------------------------------------------------------------------

def d3ca_simulated_program(loss: Loss, data: DoublyPartitioned,
                           cfg: D3CAConfig, *, local_backend: str = "ref",
                           w0=None, alpha0=None,
                           compression=None, topology=None,
                           row_gate=None, cache=None) -> EngineProgram:
    """Named-vmap grid engine.  State: (alpha (P, n_p), w_blocks (Q, m_q)).

    ``data`` may be a dense :class:`DoublyPartitioned` or a sparse
    :class:`SparseDoublyPartitioned` (padded-ELL cells); the cell
    program is the same one the mesh engines run.  ``compression`` (a
    CompressionPolicy) routes both collectives through their codecs and
    adds the error-feedback residuals to the engine state.
    ``row_gate`` ((n,) of 0/1) builds the gated incremental program:
    dual updates are restricted to gated-on rows (see
    :func:`d3ca_cell_program`)."""
    sparse = isinstance(data, SparseDoublyPartitioned)
    Pn, Qn = data.P, data.Q
    cellprog = d3ca_cell_program(loss, cfg, n=data.n, n_p=data.n_p,
                                 m_q=data.m_q, sparse=sparse,
                                 local_backend=local_backend,
                                 gated=row_gate is not None)
    key0 = jax.random.PRNGKey(cfg.seed)
    x_parts = (data.cols, data.vals) if sparse else (data.x_blocks,)
    gate_parts = (() if row_gate is None
                  else (data.alpha_to_blocks(jnp.asarray(row_gate)),))
    gdata = (key0, *x_parts, data.y_blocks, data.mask, *gate_parts)
    step = cached_build(cache, "step",
                        lambda: grid_program(cellprog, Pn, Qn,
                                             compression=compression,
                                             topology=topology))

    alpha_init = (jnp.zeros((Pn, data.n_p)) if alpha0 is None
                  else data.alpha_to_blocks(jnp.asarray(alpha0)))
    w_init = (jnp.zeros((Qn, data.m_q)) if w0 is None
              else data.w_to_blocks(jnp.asarray(w0)))
    state0 = (alpha_init, w_init)
    full0, unwrap, acct = grid_bind_state(cellprog, gdata, state0,
                                          Pn=Pn, Qn=Qn,
                                          compression=compression,
                                          topology=topology)
    local = cached_build(cache, "local",
                         lambda: grid_program(cellprog, Pn, Qn,
                                              comm_local=True))
    wrapped = full0 is not state0
    return EngineProgram(
        state=full0,
        step=lambda t, s: step(t, gdata, s),
        w_of=lambda s: data.w_from_blocks(unwrap(s)[1]),
        alpha_of=lambda s: data.alpha_from_blocks(unwrap(s)[0] * data.mask),
        comm_bytes=acct,
        local_step=lambda t, s: local(t, gdata, unwrap(s)),
        ef_of=(lambda s: s[1]) if wrapped else None)


def d3ca_simulated(loss_name: str, data: DoublyPartitioned, cfg: D3CAConfig,
                   callback=None, local_backend: str = "ref"):
    """Run D3CA on the block grid with vmap-over-cells. Returns (w, alpha)."""
    prog = d3ca_simulated_program(get_loss(loss_name), data, cfg,
                                  local_backend=local_backend)
    state = drive_with_callback(prog, cfg.outer_iters, callback,
                                pass_alpha=True)
    return prog.w_of(state), prog.alpha_of(state)


# ----------------------------------------------------------------------------
# mesh engines (shard_map sync + bounded-staleness async)
# ----------------------------------------------------------------------------

def make_d3ca_step(loss: Loss, mesh, cfg: D3CAConfig, *, n: int, n_p: int,
                   data_axis: str = "data", model_axis: str = "model",
                   local_backend: str = "ref"):
    """Build the jitted distributed D3CA outer step (sync reductions).

    Array layouts (global shapes; sharding in parens):
      x:      (n, m)    (data, model)   -- block x_[p,q] per device
      y,mask: (n,)      (data,)
      alpha:  (n,)      (data,)         -- replicated over model
      w:      (m,)      (model,)        -- replicated over data
    """
    cellprog = d3ca_cell_program(loss, cfg, n=n, n_p=n_p,
                                 local_backend=local_backend)
    run = mesh_step_fn(cellprog, mesh, data_axis=data_axis,
                       model_axis=model_axis)

    def step(t, key0, x, y, mask, alpha, w):
        (a_new, w_new), _ = run(t, (key0, x, y, mask), (alpha, w), {})
        return a_new, w_new

    return jax.jit(step, static_argnums=())


def make_d3ca_step_sparse(loss: Loss, mesh, cfg: D3CAConfig, *, n: int,
                          n_p: int, m_q: int, data_axis: str = "data",
                          model_axis: str = "model",
                          local_backend: str = "ref"):
    """Sparse-cell variant of :func:`make_d3ca_step`.

    The data block per device is the padded-ELL pair cols/vals
    (n_p, k) with block-local column ids; the primal-dual map of step 9
    becomes a scatter-add into the local w block before the psum.
    """
    cellprog = d3ca_cell_program(loss, cfg, n=n, n_p=n_p, m_q=m_q,
                                 sparse=True, local_backend=local_backend)
    run = mesh_step_fn(cellprog, mesh, data_axis=data_axis,
                       model_axis=model_axis)

    def step(t, key0, cols, vals, y, mask, alpha, w):
        (a_new, w_new), _ = run(t, (key0, cols, vals, y, mask),
                                (alpha, w), {})
        return a_new, w_new

    return jax.jit(step, static_argnums=())


def d3ca_shard_map_program(loss: Loss, sdata, cfg: D3CAConfig,
                           *, local_backend: str = "ref",
                           w0=None, alpha0=None, staleness: int = 0,
                           compression=None, overlap: bool = False,
                           topology=None, row_gate=None,
                           cache=None) -> EngineProgram:
    """Mesh engine.  State: ((alpha (n_pad,), w (m_pad,)), comm_state),
    all sharded (comm_state carries staleness rings and/or EF
    residuals).  ``sdata`` is a :class:`ShardMapData` or
    :class:`SparseShardMapData`; ``staleness=tau > 0`` selects the
    bounded-staleness async policy (tau = 0 is the sync engine);
    ``compression`` routes both collectives through their codecs;
    ``overlap=True`` dispatches reductions into donated ring slots and
    awaits them tau steps later (the overlap engine); ``topology``
    enables the hierarchical two-level reduction (pod-split mesh);
    ``row_gate`` ((n,) of 0/1) builds the gated incremental program
    (see :func:`d3ca_cell_program`)."""
    sparse = isinstance(sdata, SparseShardMapData)
    cellprog = d3ca_cell_program(
        loss, cfg, n=sdata.n, n_p=sdata.n_p,
        m_q=sdata.m_q if sparse else None, sparse=sparse,
        local_backend=local_backend, gated=row_gate is not None)
    key0 = jax.random.PRNGKey(cfg.seed)
    x_parts = (sdata.cols, sdata.vals) if sparse else (sdata.x,)
    gate_parts = (() if row_gate is None
                  else (sdata.pad_alpha(jnp.asarray(row_gate)),))
    mdata = (key0, *x_parts, sdata.y, sdata.mask, *gate_parts)
    alpha_init = (sdata.zeros_data() if alpha0 is None
                  else sdata.pad_alpha(alpha0))
    w_init = sdata.zeros_model() if w0 is None else sdata.pad_w(w0)
    step, comm0, acct = cached_build(
        cache, "step",
        lambda: mesh_program(
            cellprog, sdata.mesh, mdata, (alpha_init, w_init),
            data_axis=sdata.data_axis, model_axis=sdata.model_axis,
            staleness=staleness, compression=compression,
            overlap=overlap, topology=topology))
    local = cached_build(
        cache, "local",
        lambda: mesh_local_step(cellprog, sdata.mesh,
                                data_axis=sdata.data_axis,
                                model_axis=sdata.model_axis))
    is_overlap = bool(overlap) and staleness > 0
    return EngineProgram(
        state=((alpha_init, w_init), comm0),
        step=lambda t, s: step(t, mdata, s),
        w_of=lambda s: s[0][1][: sdata.m],
        alpha_of=lambda s: s[0][0][: sdata.n],
        comm_bytes=acct,
        local_step=lambda t, s: local(t, mdata, s[0]),
        ef_of=(lambda s: s[1]["ef"]) if "ef" in comm0 else None,
        staleness=staleness, overlap=is_overlap,
        sync_of=(lambda s: s[0]) if is_overlap else None,
        donated=is_overlap and overlap_donates())


def d3ca_distributed(loss_name: str, mesh, x, y, mask, cfg: D3CAConfig,
                     callback=None, local_backend: str = "ref"):
    """Convenience driver for the shard_map engine (single-controller).

    ``x``/``y``/``mask`` must already be padded so the mesh divides both
    axes (the unified ``Solver`` API does this automatically)."""
    loss = get_loss(loss_name)
    n, m = x.shape
    Pn = mesh.shape["data"]
    step = make_d3ca_step(loss, mesh, cfg, n=n, n_p=n // Pn,
                          local_backend=local_backend)
    key0 = jax.random.PRNGKey(cfg.seed)
    prog = EngineProgram(
        state=(jnp.zeros((n,)), jnp.zeros((m,))),
        step=lambda t, s: step(t, key0, x, y, mask, *s),
        w_of=lambda s: s[1],
        alpha_of=lambda s: s[0])
    state = drive_with_callback(prog, cfg.outer_iters, callback,
                                pass_alpha=True)
    return state[1], state[0]
