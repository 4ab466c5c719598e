"""The program's named scopes on the shard_map D3CA step, on four forced
host devices: the lowered sparse step of a 2x2 mesh carries
``repro.comm.dalpha``, ``repro.comm.w_contrib`` and ``repro.d3ca.map``
in its ops' op_name metadata, and the same step lowered without the
scopes compiles to the same program but for that metadata and steps to
bit-equal iterates.  And a solve on the mesh engine names the mesh's
shape on its ``repro.prep.bind`` span.

Executed as a subprocess by tests/test_comm_scopes.py (the device count
must be fixed before jax initializes).  Exits nonzero on failure.
"""
import contextlib
import os
import re
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax
import numpy as np

from repro.core import D3CAConfig, get_solver, prepare_shard_map_sparse
from repro.core.d3ca import d3ca_cell_program, d3ca_shard_map_program
from repro.core.engines import mesh_program
from repro.core.losses import get_loss
from repro.data import make_sparse_svm_csr
from repro.launch.mesh import make_grid_mesh
from repro.obs.trace import Tracer

SCOPES = ("repro.comm.dalpha", "repro.comm.w_contrib", "repro.d3ca.map")


@contextlib.contextmanager
def without_scopes():
    real = jax.named_scope
    jax.named_scope = lambda name: contextlib.nullcontext()
    try:
        yield
    finally:
        jax.named_scope = real


def run(sdata):
    """``(w, alpha)`` after two outer steps of a freshly built program."""
    prog = d3ca_shard_map_program(get_loss("hinge"), sdata,
                                  D3CAConfig(lam=1.0, seed=3),
                                  local_backend="pallas")
    state = prog.state
    for t in (1, 2):
        state = prog.step(t, state)
    return (np.asarray(prog.w_of(state)), np.asarray(prog.alpha_of(state)))


def compiled_text(sdata):
    """The step's program as compiled for the four devices."""
    cell = d3ca_cell_program(get_loss("hinge"), D3CAConfig(lam=1.0),
                             n=sdata.n, n_p=sdata.n_p, m_q=sdata.m_q,
                             sparse=True, local_backend="pallas")
    data = (jax.random.PRNGKey(0), sdata.cols, sdata.vals, sdata.y,
            sdata.mask)
    state = (sdata.zeros_data(), sdata.zeros_model())
    step, comm0, _ = mesh_program(cell, sdata.mesh, data, state)
    return step.lower(1, data, (state, comm0)).compile().as_text()


def strip(text):
    """The program without its metadata and debug tables."""
    return re.sub(r", metadata=\{[^}]*\}", "", text.split("\nFileNames")[0])


def bind_span_args(X, y):
    """The arguments of the ``repro.prep.bind`` span of one mesh solve."""
    solver = get_solver("d3ca")(engine="shard_map", local_backend="pallas",
                                block_format="sparse")
    tracer = Tracer()
    solver.solve("hinge", X, y, P=2, Q=2,
                 cfg=D3CAConfig(lam=1.0, outer_iters=2), tracer=tracer)
    (bind,) = tracer.spans("repro.prep.bind")
    return bind["args"]


def main():
    X, y = make_sparse_svm_csr(96, 40, density=0.2, seed=5)
    assert bind_span_args(X, y) == {"cache": "off", "mesh": "2x2"}
    sdata = prepare_shard_map_sparse(make_grid_mesh(2, 2), X, y)
    scoped = compiled_text(sdata)
    op_names = re.findall(r'op_name="([^"]*)"', scoped)
    for scope in SCOPES:
        assert any(scope in name.split("/") for name in op_names), scope
    # the w_contrib psum lies inside the map
    assert any("repro.d3ca.map/repro.comm.w_contrib" in name
               for name in op_names)
    with without_scopes():
        plain = compiled_text(sdata)
    assert not any(s in plain for s in SCOPES)
    assert strip(plain) == strip(scoped)
    w_s, a_s = run(sdata)
    with without_scopes():
        w_p, a_p = run(sdata)
    assert np.array_equal(w_s, w_p) and np.array_equal(a_s, a_p)
    assert np.abs(w_s).max() > 0
    print("ok")


if __name__ == "__main__":
    main()
