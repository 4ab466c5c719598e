"""Multi-tenant fleet subsystem: packing invariants, per-tenant
bit-equivalence with solo solves, converged-tenant freezing, warm-start
chains, and the scheduler's bucketing/warm-registry behavior.

Equivalence tests keep ``lam * n`` (and ``n * sample_frac``,
``rho * n``) powers of two: XLA strength-reduces division by a
compile-time constant into reciprocal multiplication, which is exact
only for power-of-two divisors.  The solo path bakes those products as
constants while the fleet path divides by traced per-tenant scalars,
so bit-equality holds exactly on that lattice and to float tolerance
off it (see ``test_non_pow2_products_match_to_float_tol``)."""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import (ADMMConfig, D3CAConfig, RADiSAConfig, SFKConfig,
                        get_solver)
from repro.data import make_svm_data
from repro.fleet import (FleetProblem, FleetScheduler, FleetSolver,
                         bucket_key, solo_config, stack_grid, with_tenant)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))

Pn, Qn = 2, 2
N, M = 64, 24
LAMS = (1.0, 0.5, 0.25)    # lam * n = 64 / 32 / 16


def make_problems(loss, n=N, m=M, lams=LAMS, f_stars=None):
    probs = []
    for i, lam in enumerate(lams):
        X, y = make_svm_data(n, m, seed=10 + i)
        probs.append(FleetProblem(
            tenant_id=f"t{i}", loss_name=loss, X=X, y=y, lam=lam, seed=i,
            f_star=None if f_stars is None else f_stars[i]))
    return probs


def solo_solve(name, p, cfg, *, engine="simulated", local_backend="ref",
               block_format="dense", **kw):
    s = get_solver(name)(engine=engine, local_backend=local_backend,
                         block_format=block_format)
    return s.solve(p.loss_name, p.X, p.y, P=Pn, Q=Qn,
                   cfg=solo_config(cfg, p), record_history=False, **kw)


# ---------------------------------------------------------------------------
# constructor validation / engine restriction
# ---------------------------------------------------------------------------

def test_fleet_knob_validation():
    with pytest.raises(ValueError, match="solver"):
        FleetSolver(solver="sgd")
    with pytest.raises(ValueError, match="engine"):
        FleetSolver(engine="async")
    with pytest.raises(ValueError, match="engine"):
        FleetSolver(engine="overlap")
    with pytest.raises(ValueError, match="staleness"):
        FleetSolver(engine="shard_map", staleness=2)
    with pytest.raises(ValueError, match="compression"):
        FleetSolver(compression="int8")
    with pytest.raises(ValueError, match="local_backend"):
        FleetSolver(local_backend="triton")
    with pytest.raises(ValueError, match="block_format"):
        FleetSolver(block_format="csr")
    # "sync" aliases the shard_map mesh, as in the solo registry
    assert FleetSolver(engine="sync").engine == "shard_map"


def test_solve_batch_rejects_mixed_buckets():
    a = make_problems("hinge", n=64, m=24, lams=(1.0,))
    b = make_problems("hinge", n=96, m=24, lams=(1.0,))
    with pytest.raises(ValueError, match="bucket"):
        FleetSolver().solve_batch(a + b, P=Pn, Q=Qn,
                                  cfg=D3CAConfig(outer_iters=1))


# ---------------------------------------------------------------------------
# packing invariants (pure unit tests: stay in the simulated split)
# ---------------------------------------------------------------------------

def test_bucket_key_uses_padded_shapes():
    # rows pad to a multiple of P, features to a multiple of P*Q: shapes
    # that pad equal are one bucket even when the raw shapes differ
    a = make_problems("hinge", n=63, m=22, lams=(1.0,))[0]
    b = make_problems("hinge", n=64, m=24, lams=(1.0,))[0]
    assert bucket_key(a, Pn, Qn) == bucket_key(b, Pn, Qn) \
        == ("hinge", 64, 24)
    c = make_problems("squared", n=64, m=24, lams=(1.0,))[0]
    assert bucket_key(c, Pn, Qn) != bucket_key(b, Pn, Qn)


def test_with_tenant_and_stack_grid_axis_rule():
    # the tenant axis lands right after the named block axes
    assert with_tenant((("data", "model"),)) == ((None, "data", "model"),)
    assert with_tenant(("model",)) == (None, "model")
    arrs = [np.full((3, 2, 4, 5), i, np.float32) for i in range(2)]
    assert stack_grid(arrs, ("data", "model")).shape == (3, 2, 2, 4, 5)
    ys = [np.zeros((3, 4), np.float32) for _ in range(2)]
    assert stack_grid(ys, ("data",)).shape == (3, 2, 4)
    ks = [np.zeros((2,), np.float32) for _ in range(2)]
    assert stack_grid(ks, ()).shape == (2, 2)


def test_repad_k_pads_zero_slots():
    from repro.core.partition import partition_sparse
    X, y = make_svm_data(16, 8, seed=0)
    part = partition_sparse(np.asarray(X) * (np.asarray(X) > 0), y, 2, 2,
                            m_multiple=4)
    bigger = FleetSolver._repad_k(part, part.k + 8)
    assert bigger.k == part.k + 8
    np.testing.assert_array_equal(np.asarray(bigger.cols[..., part.k:]), 0)
    np.testing.assert_array_equal(np.asarray(bigger.vals[..., part.k:]), 0.0)
    np.testing.assert_array_equal(np.asarray(bigger.vals[..., : part.k]),
                                  np.asarray(part.vals))


# ---------------------------------------------------------------------------
# grid engine: per-tenant results bit-match solo solves
# ---------------------------------------------------------------------------

#: Each case ends in its tolerance: 0.0 asserts bit equality.  On the
#: sparse-ref d3ca/admm paths XLA lowers the gather/scatter reductions
#: differently once the tenant axis is batched, so the fleet and solo
#: programs round apart in the last bits (docs/consistency.md §10).
GRID_CASES = [
    ("d3ca", D3CAConfig(local_steps=8, outer_iters=3), "hinge",
     "dense", "ref", 0.0),
    ("d3ca", D3CAConfig(local_steps=8, outer_iters=3), "logistic",
     "dense", "ref", 0.0),
    ("d3ca", D3CAConfig(local_steps=8, outer_iters=3), "hinge",
     "sparse", "ref", 1e-6),
    ("d3ca", D3CAConfig(local_steps=8, outer_iters=3), "hinge",
     "dense", "pallas", 0.0),
    ("radisa", RADiSAConfig(gamma=0.125, L=8, outer_iters=3), "squared",
     "dense", "ref", 0.0),
    ("radisa", RADiSAConfig(gamma=0.125, L=8, outer_iters=3), "hinge",
     "sparse", "ref", 0.0),
    ("radisa", RADiSAConfig(gamma=0.125, L=8, outer_iters=3), "hinge",
     "dense", "pallas", 0.0),
    ("sfk", SFKConfig(gamma=0.125, L=8, sample_frac=0.5, outer_iters=3),
     "hinge", "dense", "ref", 0.0),
    ("admm", ADMMConfig(rho=0.5, outer_iters=3), "hinge", "dense", "ref",
     0.0),
    ("admm", ADMMConfig(rho=0.5, outer_iters=3), "hinge", "sparse",
     "ref", 1e-6),
]


def assert_within(actual, expected, atol):
    """Bit equality at ``atol == 0``, else ``|a - b| <= atol``."""
    if atol == 0.0:
        np.testing.assert_array_equal(np.asarray(actual),
                                      np.asarray(expected))
    else:
        np.testing.assert_allclose(np.asarray(actual), np.asarray(expected),
                                   rtol=0, atol=atol)


@pytest.mark.fleet
@pytest.mark.parametrize(
    "name,cfg,loss,block_format,backend,atol", GRID_CASES,
    ids=[f"{c[0]}-{c[2]}-{c[3]}-{c[4]}" for c in GRID_CASES])
def test_grid_fleet_bitmatches_solo(name, cfg, loss, block_format, backend,
                                    atol):
    probs = make_problems(loss)
    fleet = FleetSolver(solver=name, local_backend=backend,
                        block_format=block_format)
    batch = fleet.solve_batch(probs, P=Pn, Q=Qn, cfg=cfg,
                              record_history=False)
    for p, res in zip(probs, batch):
        solo = solo_solve(name, p, cfg, local_backend=backend,
                          block_format=block_format)
        assert_within(res.w, solo.w, atol)
        if res.alpha is not None:
            assert_within(res.alpha, solo.alpha, atol)
        assert (res.solver, res.engine, res.block_format) == \
            (name, "simulated", block_format)


@pytest.mark.fleet
def test_non_pow2_products_match_to_float_tol():
    """Off the power-of-two lattice the solo path's constant-folded
    reciprocal differs from the fleet path's traced division in the
    last bit; results agree to float tolerance.  Two instances: a
    non-pow2 ``lam * n`` (= 48), and admm's squared prox, whose
    ``1 + 2c`` denominator (1.125) is never a power of two."""
    probs = make_problems("hinge", n=96, lams=(0.5,))
    cfg = D3CAConfig(local_steps=8, outer_iters=3)
    res = FleetSolver().solve_batch(probs, P=Pn, Q=Qn, cfg=cfg,
                                    record_history=False)[0]
    solo = solo_solve("d3ca", probs[0], cfg)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(solo.w),
                               rtol=0, atol=1e-6)

    probs = make_problems("squared", lams=(0.5,))
    cfg = ADMMConfig(rho=0.5, outer_iters=3)
    res = FleetSolver(solver="admm").solve_batch(
        probs, P=Pn, Q=Qn, cfg=cfg, record_history=False)[0]
    solo = solo_solve("admm", probs[0], cfg)
    np.testing.assert_allclose(np.asarray(res.w), np.asarray(solo.w),
                               rtol=0, atol=1e-6)


# ---------------------------------------------------------------------------
# convergence freezing + warm starts
# ---------------------------------------------------------------------------

@pytest.mark.fleet
def test_frozen_tenant_state_is_exact():
    """A tenant frozen at iteration k bit-equals a solo solve truncated
    at k outer iterations -- jnp.where carries its state untouched."""
    from repro.core import objective, serial_sdca
    probs = make_problems("hinge")
    f_stars = []
    for p in probs:
        w_ref, _ = serial_sdca("hinge", p.X, p.y, lam=p.lam, epochs=200)
        f_stars.append(float(objective("hinge", p.X, p.y, w_ref, p.lam)))
    probs = [FleetProblem(tenant_id=p.tenant_id, loss_name=p.loss_name,
                          X=p.X, y=p.y, lam=p.lam, seed=p.seed,
                          f_star=f_stars[i]) for i, p in enumerate(probs)]
    cfg = D3CAConfig(local_steps=16, outer_iters=30)
    batch = FleetSolver().solve_batch(probs, P=Pn, Q=Qn, cfg=cfg,
                                      tol=0.05, check_every=2)
    assert any(r.converged for r in batch)
    iters = {r.iters for r in batch}
    for p, res in zip(probs, batch):
        if not res.converged:
            continue
        solo = solo_solve(
            "d3ca", p, D3CAConfig(local_steps=16, outer_iters=res.iters))
        np.testing.assert_array_equal(np.asarray(res.w),
                                      np.asarray(solo.w))
        assert res.history[-1]["rel_opt"] < 0.05
    # tenants froze at different segment boundaries (the mask matters)
    assert len(iters) > 1 or not all(r.converged for r in batch)


@pytest.mark.fleet
def test_warm_start_chain_bitmatches_solo_chain():
    probs = make_problems("hinge")
    cfg = D3CAConfig(local_steps=8, outer_iters=3)
    fleet = FleetSolver()
    first = fleet.solve_batch(probs, P=Pn, Q=Qn, cfg=cfg,
                              record_history=False)
    second = fleet.solve_batch(probs, P=Pn, Q=Qn, cfg=cfg,
                               warm_starts=first, record_history=False)
    for p, res in zip(probs, second):
        s1 = solo_solve("d3ca", p, cfg)
        s2 = solo_solve("d3ca", p, cfg, warm_start=s1)
        np.testing.assert_array_equal(np.asarray(res.w), np.asarray(s2.w))
        np.testing.assert_array_equal(np.asarray(res.alpha),
                                      np.asarray(s2.alpha))


# ---------------------------------------------------------------------------
# scheduler: bucketing, chunking, warm registry, callbacks
# ---------------------------------------------------------------------------

@pytest.mark.fleet
def test_scheduler_buckets_and_matches_solo():
    cfg = D3CAConfig(local_steps=8, outer_iters=3)
    small = make_problems("hinge", n=64, m=24)
    big = make_problems("hinge", n=128, m=24, lams=(0.5, 0.25))
    big = [FleetProblem(tenant_id=f"big{i}", loss_name=p.loss_name,
                        X=p.X, y=p.y, lam=p.lam, seed=p.seed)
           for i, p in enumerate(big)]
    sched = FleetScheduler(P=Pn, Q=Qn, solver="d3ca", cfg=cfg)
    for p in small + big:
        sched.submit(p)
    assert sched.pending() == 5
    assert len(sched.buckets()) == 2
    results = sched.run()
    assert sched.pending() == 0
    assert list(results) == [p.tenant_id for p in small + big]
    for p in small + big:
        solo = solo_solve("d3ca", p, cfg)
        np.testing.assert_array_equal(np.asarray(results[p.tenant_id].w),
                                      np.asarray(solo.w))


@pytest.mark.fleet
def test_scheduler_chunking_and_warm_registry():
    cfg = D3CAConfig(local_steps=8, outer_iters=3)
    probs = make_problems("hinge")
    seen = []
    sched = FleetScheduler(P=Pn, Q=Qn, solver="d3ca", cfg=cfg,
                           max_tenants=2,
                           on_result=lambda tid, res: seen.append(tid))
    for p in probs:
        sched.submit(p)
    first = sched.run()
    assert seen == [p.tenant_id for p in probs]
    # round 2 warm-starts every tenant from its round-1 result
    for p in probs:
        sched.submit(p)
    second = sched.run()
    for p in probs:
        assert sched.warm_start_of(p.tenant_id) is not None
        s1 = solo_solve("d3ca", p, cfg)
        np.testing.assert_array_equal(np.asarray(first[p.tenant_id].w),
                                      np.asarray(s1.w))
        s2 = solo_solve("d3ca", p, cfg, warm_start=s1)
        np.testing.assert_array_equal(np.asarray(second[p.tenant_id].w),
                                      np.asarray(s2.w))


def test_fleet_obs_hooks():
    from repro.obs import Registry, Tracer
    tr, reg = Tracer(), Registry()
    probs = make_problems("hinge", lams=(1.0, 0.5))
    sched = FleetScheduler(P=Pn, Q=Qn, solver="d3ca",
                           cfg=D3CAConfig(local_steps=4, outer_iters=2),
                           tracer=tr, registry=reg)
    for p in probs:
        sched.submit(p)
    sched.run()
    names = {s["name"] for s in tr.spans()}
    assert {"fleet/pack", "fleet/step", "fleet/unpack"} <= names
    gauges = reg.snapshot()["gauges"]
    for want in ("fleet/bucket_tenants", "fleet/tenants", "fleet/active"):
        assert any(k.startswith(want) for k in gauges), (want, gauges)


# ---------------------------------------------------------------------------
# shard_map mesh (subprocess: forced 4 x 2 device grid)
# ---------------------------------------------------------------------------

@pytest.mark.fleet
@pytest.mark.shard_map
def test_mesh_fleet_matches_solo():
    """Per-tenant fleet-vs-solo equivalence on the shard_map mesh: bit
    for sparse and hinge-path dense, <= 1e-6 for the dense smooth-loss
    matvec cases (see helpers/fleet_equiv.py)."""
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "helpers",
                                      "fleet_equiv.py")],
        env=ENV, timeout=600, capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
