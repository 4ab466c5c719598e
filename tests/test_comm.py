"""Engine API v2: CommSchedule declaration contract, collective
execution under named-vmap grids, the StaleComm FIFO semantics
(value applied at t is the reduction computed at max(1, t - tau)),
the OverlapComm executor (identical consumption contract, overlapped
wire), and the hierarchical two-level reduction (set_topology).

Everything here runs on ONE device: the grid engine uses named vmap
axes, and the mesh/staleness tests use a 1x1 mesh (collectives become
identities there, which isolates the delay semantics)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.comm import (Collective, CommSchedule, OverlapComm,
                             StaleComm, SyncComm, hier_ef_names)
from repro.core.comm_model import Topology
from repro.core.compress import get_codec
from repro.core.engines import CellProgram, grid_program, mesh_program
from repro.launch.mesh import make_mesh


# ---------------------------------------------------------------------------
# schedule declaration contract
# ---------------------------------------------------------------------------

def test_schedule_declaration():
    sched = (CommSchedule()
             .psum("rhs", axis="data")
             .pmean("dalpha", axis="model")
             .allgather("alpha", axis="data"))
    assert sched.names == ("rhs", "dalpha", "alpha")
    assert "rhs" in sched and "nope" not in sched
    assert sched["rhs"].op == "psum"
    assert sched["dalpha"].result_axis == "data"
    assert sched["rhs"].result_axis == "model"


def test_schedule_rejects_duplicates_and_bad_axes():
    with pytest.raises(ValueError, match="declared twice"):
        CommSchedule().psum("x", axis="data").pmean("x", axis="model")
    with pytest.raises(ValueError, match="axis"):
        CommSchedule().psum("x", axis="rows")
    with pytest.raises(ValueError, match="op"):
        Collective("x", "allreduce", "data")


def test_schedule_unknown_lookup_message():
    sched = CommSchedule().psum("declared", axis="data")
    with pytest.raises(KeyError, match="not declared in this CommSchedule"):
        sched["other"]


def test_comm_contract_checks():
    sched = CommSchedule().psum("a", axis="data").psum("b", axis="model")
    axis_map = {"data": ("d",), "model": ("m",)}

    def cell_twice(x):
        comm = SyncComm(sched, axis_map, {"data": 2, "model": 1})
        comm("a", x)
        return comm("a", x)                 # same point twice -> error

    with pytest.raises(ValueError, match="executed twice"):
        jax.vmap(jax.vmap(cell_twice, axis_name="m"), axis_name="d")(
            jnp.ones((2, 1)))

    def cell_partial(x):
        comm = SyncComm(sched, axis_map, {"data": 2, "model": 1})
        out = comm("a", x)
        comm.finalize()                     # "b" never executed -> error
        return out

    with pytest.raises(ValueError, match="never executed"):
        jax.vmap(jax.vmap(cell_partial, axis_name="m"), axis_name="d")(
            jnp.ones((2, 1)))


# ---------------------------------------------------------------------------
# collective execution under named vmap (the grid engine's substrate)
# ---------------------------------------------------------------------------

def test_sync_comm_under_named_vmap():
    sched = (CommSchedule()
             .psum("s", axis="data")
             .pmean("m", axis="model")
             .allgather("g", axis="data"))
    axis_map = {"data": ("d",), "model": ("m",)}
    vals = jnp.arange(6.0).reshape(3, 2)        # grid P=3, Q=2

    def cell(x):
        comm = SyncComm(sched, axis_map, {"data": 3, "model": 2})
        out = (comm("s", x), comm("m", x), comm("g", x),
               comm.axis_index("data"), comm.axis_index("model"))
        comm.finalize()
        assert comm.axis_size("data") == 3
        return out

    s, m, g, p, q = jax.vmap(jax.vmap(cell, axis_name="m"),
                             axis_name="d")(vals)
    np.testing.assert_allclose(np.asarray(s), np.asarray(
        vals.sum(axis=0, keepdims=True).repeat(3, 0)))
    np.testing.assert_allclose(np.asarray(m), np.asarray(
        vals.mean(axis=1, keepdims=True).repeat(2, 1)))
    assert g.shape == (3, 2, 3)                  # per-cell gather over data
    np.testing.assert_allclose(np.asarray(g[0, 1]), np.asarray(vals[:, 1]))
    np.testing.assert_array_equal(np.asarray(p), [[0, 0], [1, 1], [2, 2]])
    np.testing.assert_array_equal(np.asarray(q), [[0, 1], [0, 1], [0, 1]])


# ---------------------------------------------------------------------------
# StaleComm FIFO semantics via the mesh executor on a 1x1 mesh
# ---------------------------------------------------------------------------

def _delay_program():
    """A cell whose single collective carries f(t) = t as payload; the
    state records what the comm handed back, so the returned sequence
    exposes the delay directly."""
    sched = CommSchedule().psum("probe", axis="data")

    def cell(comm, t, data, state):
        seen = comm("probe", jnp.float32(t) * data)
        return seen
    # data: a scalar-per-cell array; state: the last value seen
    return CellProgram(sched, cell, data_specs=(None,), state_specs=(None,))


@pytest.mark.parametrize("tau", [1, 2, 3])
def test_stale_comm_bounded_delay(tau):
    mesh = make_mesh((1, 1), ("data", "model"))
    cellprog = _delay_program()
    data = jnp.ones((1,))
    state0 = jnp.zeros((1,))
    step, comm0, acct = mesh_program(cellprog, mesh, data, state0,
                                     staleness=tau)
    assert set(comm0) == {"stale"}
    assert comm0["stale"]["probe"].shape == (1, 1, tau, 1)
    # wire accounting comes back from every engine binding: the probe
    # payload is one f32 per cell per step
    assert acct["collectives"]["probe"]["bytes_per_step"] == 4
    assert acct["bytes_per_step"] == acct["uncompressed_bytes_per_step"]
    state = (state0, comm0)
    seen = []
    for t in range(1, 9):
        state = step(t, data, state)
        seen.append(float(state[0][0]))
    # contract: value applied at t is the reduction computed at
    # max(1, t - tau)
    expect = [float(max(1, t - tau)) for t in range(1, 9)]
    assert seen == expect, (tau, seen, expect)


def test_stale_tau0_is_sync():
    mesh = make_mesh((1, 1), ("data", "model"))
    cellprog = _delay_program()
    data = jnp.ones((1,))
    state0 = jnp.zeros((1,))
    step, comm0, _ = mesh_program(cellprog, mesh, data, state0, staleness=0)
    assert comm0 == {}
    state = (state0, comm0)
    for t in range(1, 5):
        state = step(t, data, state)
        assert float(state[0][0]) == float(t)    # no delay at tau = 0


def test_stale_comm_rejects_negative_tau():
    with pytest.raises(ValueError, match="must be >= 0"):
        StaleComm(CommSchedule(), {"data": ("d",), "model": ("m",)},
                  {"data": 1, "model": 1}, tau=-1, t=1)


def test_stale_warmup_pins_first_reduction():
    """Warm-up contract (see the StaleComm docstring): at t = 1 every
    ring slot is seeded with the FIRST reduction, so steps 1..tau+1 all
    consume step 1's value -- never zeros from initialization, never a
    partially-filled ring."""
    tau = 3
    mesh = make_mesh((1, 1), ("data", "model"))
    data = jnp.ones((1,))
    step, comm0, _ = mesh_program(_delay_program(), mesh, data,
                                  jnp.zeros((1,)), staleness=tau)
    state = (jnp.zeros((1,)), comm0)
    seen = []
    for t in range(1, tau + 3):
        state = step(t, data, state)
        seen.append(float(state[0][0]))
    # steps 1..tau+1 consume step 1's value; tau+2 consumes step 2's
    assert seen[:tau + 1] == [1.0] * (tau + 1)
    assert seen[tau + 1] == 2.0


# ---------------------------------------------------------------------------
# OverlapComm: same consumption contract, overlapped wire
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("tau", [0, 2])
def test_overlap_comm_matches_stale_delay(tau):
    """The overlap engine changes wall-clock, never numerics: at every
    tau its per-step outputs equal StaleComm's bit for bit (tau = 0 is
    the sync engine)."""
    mesh = make_mesh((1, 1), ("data", "model"))
    data = jnp.ones((1,))
    state0 = jnp.zeros((1,))
    step_s, comm_s, _ = mesh_program(_delay_program(), mesh, data, state0,
                                     staleness=tau)
    step_o, comm_o, _ = mesh_program(_delay_program(), mesh, data, state0,
                                     staleness=tau, overlap=True)
    assert jax.tree_util.tree_structure(comm_s) \
        == jax.tree_util.tree_structure(comm_o)
    ss, so = (state0, comm_s), (state0, comm_o)
    for t in range(1, 8):
        ss, so = step_s(t, data, ss), step_o(t, data, so)
        assert float(ss[0][0]) == float(so[0][0]), t


def test_overlap_comm_class_contract():
    kw = dict(tau=2, t=1)
    oc = OverlapComm(CommSchedule(), {"data": ("d",), "model": ("m",)},
                     {"data": 1, "model": 1}, **kw)
    assert oc.overlap and isinstance(oc, StaleComm)
    stale = StaleComm(CommSchedule(), {"data": ("d",), "model": ("m",)},
                      {"data": 1, "model": 1}, **kw)
    assert not getattr(stale, "overlap", False)


def test_wire_bytes_additive_across_executors():
    """Byte accounting is additive, not policy-dependent: the staleness
    ring only re-times consumption, so sync / stale / overlap report
    identical totals for the identity wire."""
    mesh = make_mesh((1, 1), ("data", "model"))
    data = jnp.ones((1,))
    state0 = jnp.zeros((1,))
    accts = {}
    for label, kw in (("sync", dict(staleness=0)),
                      ("stale", dict(staleness=2)),
                      ("overlap", dict(staleness=2, overlap=True))):
        _, _, acct = mesh_program(_delay_program(), mesh, data, state0, **kw)
        accts[label] = acct
    base = accts["sync"]
    for label, acct in accts.items():
        assert acct["bytes_per_step"] == base["bytes_per_step"], label
        assert acct["bytes_per_step"] == acct["uncompressed_bytes_per_step"]
        assert {n: c["bytes_per_step"]
                for n, c in acct["collectives"].items()} \
            == {n: c["bytes_per_step"]
                for n, c in base["collectives"].items()}, label


# ---------------------------------------------------------------------------
# hierarchical two-level reduction (set_topology)
# ---------------------------------------------------------------------------

def _hier_run(cell, pods, per_pod, payload):
    """Run `cell(x)` under a (pod, d) two-level named-vmap split."""
    return jax.vmap(jax.vmap(cell, axis_name="d"),
                    axis_name="pod")(payload.reshape(pods, per_pod))


def test_hierarchical_psum_matches_flat():
    """identity topology codec: intra-pod psum + cross-pod psum == the
    flat psum over all cells (up to f32 reassociation)."""
    sched = CommSchedule().psum("s", axis="data").pmean("m", axis="data")
    axis_map = {"data": ("pod", "d"), "model": ()}
    sizes = {"data": 8, "model": 1}
    vals = jnp.arange(8.0) + 0.25

    def cell(x):
        comm = SyncComm(sched, axis_map, sizes)
        comm.set_topology(Topology(pods=2), get_codec("identity"))
        out = comm("s", x), comm("m", x)
        comm.finalize()
        return out

    s, m = _hier_run(cell, 2, 4, vals)
    np.testing.assert_allclose(np.asarray(s).ravel(),
                               float(vals.sum()), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(m).ravel(),
                               float(vals.mean()), rtol=1e-6)


def test_hierarchical_stateful_codec_threads_ef():
    """A stateful cross-pod codec consumes hier_ef_in and emits
    hier_ef_out; a missing residual is a loud KeyError."""
    sched = CommSchedule().psum("s", axis="data")
    axis_map = {"data": ("pod", "d"), "model": ()}
    sizes = {"data": 4, "model": 1}
    codec = get_codec("int8")
    assert codec.stateful
    assert hier_ef_names(sched, Topology(pods=2, codec="int8")) == ("s",)
    assert hier_ef_names(sched, Topology(pods=2)) == ()        # stateless
    assert hier_ef_names(sched, None) == ()

    def cell(x, ef):
        comm = SyncComm(sched, axis_map, sizes)
        comm.set_topology(Topology(pods=2, codec="int8"), codec,
                          ef={"s": ef})
        out = comm("s", x)
        comm.finalize()
        return out, comm.hier_ef_out["s"]

    vals = jnp.arange(4.0)
    out, ef_out = jax.vmap(jax.vmap(cell, axis_name="d"),
                           axis_name="pod")(
        vals.reshape(2, 2), jnp.zeros((2, 2)))
    assert jnp.isfinite(out).all() and ef_out.shape == (2, 2)

    def cell_no_ef(x):
        comm = SyncComm(sched, axis_map, sizes)
        comm.set_topology(Topology(pods=2, codec="int8"), codec)
        return comm("s", x)

    with pytest.raises(KeyError, match="error-feedback residual"):
        jax.vmap(jax.vmap(cell_no_ef, axis_name="d"),
                 axis_name="pod")(vals.reshape(2, 2))


def test_hierarchical_needs_two_level_axis_split():
    sched = CommSchedule().psum("s", axis="data")

    def cell(x):
        comm = SyncComm(sched, {"data": ("d",), "model": ()},
                        {"data": 2, "model": 1})
        comm.set_topology(Topology(pods=2), get_codec("identity"))
        return comm("s", x)

    with pytest.raises(ValueError, match="two-level axis split"):
        jax.vmap(cell, axis_name="d")(jnp.ones((2,)))


# ---------------------------------------------------------------------------
# grid executor: dim-specs drive replication/unreplication
# ---------------------------------------------------------------------------

def test_grid_program_specs_roundtrip():
    sched = CommSchedule().psum("col", axis="data").pmean("row", axis="model")

    def cell(comm, t, data, state):
        x_b, = data                      # (n_p, m_q) cell of the grid
        a_b, w_b = state
        a_new = a_b + comm("row", x_b.sum(axis=1))    # varies over data
        w_new = comm("col", x_b.sum(axis=0)) + w_b    # varies over model
        return a_new, w_new

    cellprog = CellProgram(sched, cell,
                           data_specs=((("data", "model"),)),
                           state_specs=((("data",), ("model",))))
    Pn, Qn, n_p, m_q = 3, 2, 4, 5
    x = jnp.arange(float(Pn * Qn * n_p * m_q)).reshape(Pn, Qn, n_p, m_q)
    step = grid_program(cellprog, Pn, Qn)
    a, w = step(1, (x,), (jnp.zeros((Pn, n_p)), jnp.zeros((Qn, m_q))))
    assert a.shape == (Pn, n_p) and w.shape == (Qn, m_q)
    np.testing.assert_allclose(
        np.asarray(a), np.asarray(x.sum(axis=3).mean(axis=1)))
    np.testing.assert_allclose(
        np.asarray(w), np.asarray(x.sum(axis=2).sum(axis=0)))


# ---------------------------------------------------------------------------
# solver-level knob validation (single device; no solve is run)
# ---------------------------------------------------------------------------

def test_solver_staleness_validation():
    from repro.core import get_solver
    cls = get_solver("d3ca")
    assert cls(engine="async", staleness=3).staleness == 3
    assert cls(engine="overlap", staleness=3).staleness == 3
    assert cls(engine="sync").engine == "shard_map"     # alias
    with pytest.raises(ValueError, match="must be >= 0"):
        cls(engine="async", staleness=-1)
    with pytest.raises(ValueError, match="needs engine='async'"):
        cls(engine="shard_map", staleness=2)
    with pytest.raises(ValueError, match="needs engine='async'"):
        cls(engine="simulated", staleness=1)


def test_solver_topology_validation():
    from repro.core import get_solver
    from repro.data import make_svm_data
    cls = get_solver("d3ca")
    s = cls(engine="overlap", staleness=2, topology="pods=2:int8")
    assert s.topology.pods == 2 and s.topology_spec == "pods=2:int8:ring"
    assert cls().topology is None and cls().topology_spec is None
    with pytest.raises(ValueError, match="spec"):
        cls(topology="2pods")
    # pod count must divide P at program-build time
    X, y = make_svm_data(24, 8, seed=0)
    bad = cls(engine="simulated", topology="pods=2")
    with pytest.raises(ValueError, match="divide"):
        bad.program("hinge", X, y, P=3, Q=1)
