"""The primal and dual objectives evaluated on the dense device blocks
(``partition.block_objective`` / ``block_dual_objective``): against a
float64 NumPy evaluation on grids whose row and feature counts leave
padding, and inside ``Solver.solve`` against the solve loop's evaluation
on the caller's X, which every other block format and engine keeps."""
import dataclasses

import numpy as np
import pytest

from repro.core import D3CAConfig, RADiSAConfig, get_solver
from repro.core.losses import get_loss
from repro.core.partition import partition
from repro.core.solver import Solver
from repro.data import make_svm_data
from repro.data.sparse import make_sparse_svm_csr

N, M = 53, 29

#: float64 value f(z, y) and conjugate phi*(-a) of each loss
VALUE = {"hinge": lambda z, y: np.maximum(0.0, 1.0 - y * z),
         "squared": lambda z, y: (z - y) ** 2,
         "logistic": lambda z, y: np.logaddexp(0.0, -y * z)}


def _xlogx(t):
    return np.where(t > 0, t * np.log(np.maximum(t, 1e-300)), 0.0)


CONJ = {"hinge": lambda a, y: -a * y,
        "squared": lambda a, y: -a * y + a * a / 4.0,
        "logistic": lambda a, y: _xlogx(a * y) + _xlogx(1.0 - a * y)}


def iterates(loss_name, y, seed):
    """A primal iterate and a feasible dual one (a y in (0, 1) for the
    box-constrained losses)."""
    rng = np.random.default_rng(seed)
    w = rng.normal(size=M).astype(np.float32) * 0.3
    u = rng.uniform(0.05, 0.95, size=N)
    alpha = (y * u if loss_name != "squared"
             else rng.normal(size=N)).astype(np.float32)
    return w, alpha


@pytest.mark.parametrize("loss_name", ["hinge", "squared", "logistic"])
@pytest.mark.parametrize("grid", [(3, 2), (4, 3)])
def test_block_objectives_match_float64(loss_name, grid):
    # 53 rows and 29 features: neither divides P or P * Q, so the blocks
    # carry padded rows (mask 0) and padded zero feature columns
    P, Q = grid
    X, y = make_svm_data(N, M, seed=1)
    data = partition(X, y, P, Q, m_multiple=P * Q)
    assert data.n_p * P > N and data.m_q * Q > M
    w, alpha = iterates(loss_name, y, seed=P)
    lam = 0.1
    loss = get_loss(loss_name)
    X64, y64 = X.astype(np.float64), y.astype(np.float64)
    w64, a64 = w.astype(np.float64), alpha.astype(np.float64)
    primal = VALUE[loss_name](X64 @ w64, y64).mean() + lam / 2 * w64 @ w64
    v = X64.T @ a64 / (lam * N)
    dual = -CONJ[loss_name](a64, y64).mean() - lam / 2 * v @ v
    np.testing.assert_allclose(float(data.objective(loss, w, lam)), primal,
                               rtol=1e-6)
    np.testing.assert_allclose(float(data.dual_objective(loss, alpha, lam)),
                               dual, rtol=1e-6)


def host_operands(monkeypatch):
    """Hand ``Solver.solve``'s observation programs without the block
    evaluators: the solve loop's path on the caller's X."""
    built = Solver.program

    def program(self, *args, **kw):
        prog = built(self, *args, **kw)
        return dataclasses.replace(prog, primal_of=None, dual_of=None)
    monkeypatch.setattr(Solver, "program", program)


def test_d3ca_solve_on_blocks_matches_host_operands(monkeypatch):
    X, y = make_svm_data(N, M, seed=2)
    cfg = D3CAConfig(lam=1e-1, outer_iters=12, local_steps=8)
    solver = get_solver("d3ca")(engine="simulated")
    assert solver.program("hinge", X, y, P=3, Q=2, cfg=cfg).dual_of
    # a tolerance the gap crosses inside the budget: the stop is decided
    # on the evaluations compared
    tol = 0.3
    blocks = solver.solve("hinge", X, y, P=3, Q=2, cfg=cfg, tol=tol)
    host_operands(monkeypatch)
    host = solver.solve("hinge", X, y, P=3, Q=2, cfg=cfg, tol=tol)
    assert blocks.converged and host.converged
    assert blocks.iters == host.iters < cfg.outer_iters
    for b, h in zip(blocks.history, host.history, strict=True):
        for key in ("objective", "duality_gap"):
            assert b[key] == pytest.approx(h[key], rel=1e-6)
    np.testing.assert_array_equal(np.asarray(blocks.w), np.asarray(host.w))


def test_radisa_solve_evaluates_only_the_primal_on_blocks(monkeypatch):
    X, y = make_svm_data(N, M, seed=3)
    cfg = RADiSAConfig(lam=1e-1, outer_iters=4)
    solver = get_solver("radisa")(engine="simulated")
    prog = solver.program("hinge", X, y, P=2, Q=2, cfg=cfg)
    assert prog.primal_of is not None and prog.dual_of is None
    blocks = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg)
    host_operands(monkeypatch)
    host = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg)
    assert blocks.iters == host.iters == cfg.outer_iters
    for b, h in zip(blocks.history, host.history, strict=True):
        assert "duality_gap" not in b
        assert b["objective"] == pytest.approx(h["objective"], rel=1e-6)


@pytest.mark.parametrize("engine,fmt", [("simulated", "sparse"),
                                        ("shard_map", "dense")])
def test_other_paths_keep_the_host_evaluation(engine, fmt):
    X, y = (make_sparse_svm_csr(N, M, density=0.2, seed=0) if fmt == "sparse"
            else make_svm_data(N, M, seed=0))
    solver = get_solver("d3ca")(engine=engine, block_format=fmt)
    prog = solver.program("hinge", X, y, P=1, Q=1,
                          cfg=D3CAConfig(lam=1e-1, outer_iters=2))
    assert prog.primal_of is None and prog.dual_of is None
