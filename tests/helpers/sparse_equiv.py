"""Sparse block format on 8 forced host devices: every solver under
(engine="shard_map", block_format="sparse") must match
(engine="simulated", block_format="dense", local_backend="ref") on the
same instance -- including a non-dividing m (P*Q padding) and an
all-zero feature-block column -- for both local backends, from a
CSRMatrix input that is never densified on the solve path.

Also asserts the device-side ELL buffers scale with nnz, not m_q.

Executed as a subprocess by tests/test_sparse.py (the device count must
be fixed before jax initializes).  Prints max-abs diffs; exits nonzero
on failure.
"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax.numpy as jnp

from repro.core import (ADMMConfig, D3CAConfig, RADiSAConfig, get_solver,
                        prepare_shard_map_sparse)
from repro.data import csr_from_dense, make_sparse_svm_data
from repro.launch.mesh import make_mesh


def main():
    Pn, Qn = 4, 2
    lam = 1.0
    # m = 41: P*Q = 8 does not divide it -> padded to 48, m_q = 24.
    # Zeroing columns 24+ makes feature block q=1 entirely zero.
    X, y = make_sparse_svm_data(120, 41, density=0.15, seed=7)
    X[:, 24:] = 0.0
    Xcsr = csr_from_dense(X)

    fails = 0

    def check(name, a, b, tol=2e-4):
        nonlocal fails
        d = float(jnp.abs(a - b).max())
        print(f"{name} {d:.3e}")
        if not d < tol:
            fails += 1

    cases = [
        ("d3ca", D3CAConfig(lam=lam, outer_iters=3, local_steps=12)),
        ("d3ca_beta", D3CAConfig(lam=lam, outer_iters=2, local_steps=12,
                                 step_mode="beta")),
        ("radisa", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("radisa_avg", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3,
                                    L=12, variant="avg")),
        ("admm", ADMMConfig(lam=lam, rho=lam, outer_iters=4)),
    ]
    for label, cfg in cases:
        name = label.split("_")[0]
        base = get_solver(name)(engine="simulated", local_backend="ref")
        rb = base.solve("hinge", X, y, P=Pn, Q=Qn, cfg=cfg,
                        record_history=False)
        backends = ("ref",) if name == "admm" else ("ref", "pallas")
        for backend in backends:
            dist = get_solver(name)(engine="shard_map",
                                    local_backend=backend,
                                    block_format="sparse")
            rd = dist.solve("hinge", Xcsr, y, P=Pn, Q=Qn, cfg=cfg,
                            record_history=False)
            check(f"{label}_{backend}_w", rb.w, rd.w)
            if rb.alpha is not None:
                check(f"{label}_{backend}_alpha", rb.alpha, rd.alpha)

    # device buffers are ELL-sized: k ~ max row nnz, nowhere near m_q
    mesh = make_mesh((Pn, Qn), ("data", "model"))
    sdata = prepare_shard_map_sparse(mesh, Xcsr, y, m_multiple=Pn * Qn)
    print(f"ell k={sdata.k} m_q={sdata.m_q} "
          f"cols={sdata.cols.shape} vals={sdata.vals.shape}")
    assert sdata.cols.shape == (sdata.n_pad, Qn * sdata.k)
    if not sdata.k < sdata.m_q:
        print("ELL width k does not beat m_q")
        fails += 1

    raise SystemExit(fails)


if __name__ == "__main__":
    main()
