"""Unified solver API on 8 forced host devices.

Three modes, selected by argv[1] (default "sync"):

  * ``sync``  -- every solver must produce the same iterates under
    (engine="shard_map", local_backend="pallas") as under
    (engine="simulated", local_backend="ref"), including when P*Q does
    not divide m (both engines pad identically).  Also the regression
    check that ``make_radisa_step`` fails loudly instead of silently
    truncating feature columns when P does not divide m_q.
  * ``async`` -- the Engine API v2 staleness contract: for all three
    solvers x both block formats, engine="async" with staleness=0 must
    match engine="shard_map" to 1e-8 (it is the same program), and a
    staleness=2 run must still converge (duality gap / objective under
    a loose threshold).
  * ``compress`` -- the compressed-communication contract: for all
    three solvers x both block formats (and the pallas backend),
    compression=None and the identity codec produce bit-identical
    iterates on the mesh engines (diff 0.0); the identity accounting
    reports exactly the uncompressed bytes; compression composes with
    the async engine's staleness rings; and EF-int8 D3CA reaches the
    uncompressed duality gap within 2x the iterations.
  * ``overlap`` -- the communication-overlap contract: for all three
    solvers x both block formats (and the pallas backend),
    engine="overlap" with staleness=0 is BIT-identical (diff 0.0) to
    engine="shard_map", and at staleness=2 its trajectory equals
    engine="async" at the same tau (overlap changes wall-clock, never
    numerics).  Composition: overlap + int8 at tau=2 equals async +
    int8 at tau=2 bit for bit (EF residuals ride the dispatch step);
    wire accounting is additive (sync == async == overlap byte totals
    for the identity wire); and a hierarchical topology run
    (pods=2:int8) under overlap still converges.

Executed as a subprocess by tests/test_solver.py / test_compress.py
(the device count must be fixed before jax initializes).  Prints
max-abs diffs; exits nonzero on failure.
"""
import os
import sys
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax.numpy as jnp

from repro.core import (ADMMConfig, D3CAConfig, RADiSAConfig, SFKConfig,
                        get_loss, get_solver, make_radisa_step,
                        objective)
from repro.data import make_svm_data
from repro.launch.mesh import make_mesh

Pn, Qn = 4, 2


def main_async():
    """async engine: tau=0 == shard_map at 1e-8; tau>0 still converges."""
    lam = 1.0
    X, y = make_svm_data(120, 42, seed=1)

    fails = 0

    def check(name, a, b, tol=1e-8):
        nonlocal fails
        d = float(jnp.abs(a - b).max())
        print(f"{name} {d:.3e}")
        if not d <= tol:
            fails += 1

    cases = [
        ("d3ca", D3CAConfig(lam=lam, outer_iters=3, local_steps=12)),
        ("radisa", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("sfk", SFKConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("admm", ADMMConfig(lam=lam, rho=lam, outer_iters=4)),
    ]
    for block_format in ("dense", "sparse"):
        for name, cfg in cases:
            sync = get_solver(name)(engine="shard_map",
                                    block_format=block_format)
            asn = get_solver(name)(engine="async", staleness=0,
                                   block_format=block_format)
            rs = sync.solve("hinge", X, y, P=Pn, Q=Qn, cfg=cfg,
                            record_history=False)
            ra = asn.solve("hinge", X, y, P=Pn, Q=Qn, cfg=cfg,
                           record_history=False)
            check(f"{name}_{block_format}_tau0_w", rs.w, ra.w)
            if rs.alpha is not None:
                check(f"{name}_{block_format}_tau0_alpha", rs.alpha, ra.alpha)

    # the pallas local backend runs inside the async cells unchanged
    cfg = D3CAConfig(lam=lam, outer_iters=3, local_steps=12)
    rs = get_solver("d3ca")(engine="shard_map",
                            local_backend="pallas").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    ra = get_solver("d3ca")(engine="async", staleness=0,
                            local_backend="pallas").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    check("d3ca_pallas_tau0_w", rs.w, ra.w)

    # tau > 0 convergence smoke: stale reductions still close the
    # duality gap (d3ca) / reduce the objective (radisa)
    res = get_solver("d3ca")(engine="async", staleness=2).solve(
        "hinge", X, y, P=Pn, Q=Qn,
        cfg=D3CAConfig(lam=lam, outer_iters=12))
    gap = res.history[-1]["duality_gap"]
    print(f"d3ca_tau2_gap {gap:.3e}")
    if not gap < 0.5:
        fails += 1
    # stale gradients need a smaller step size than the sync smoke
    res = get_solver("radisa")(engine="async", staleness=2).solve(
        "hinge", X, y, P=Pn, Q=Qn,
        cfg=RADiSAConfig(lam=lam, gamma=0.01, outer_iters=12))
    f0 = float(objective("hinge", X, y, jnp.zeros(X.shape[1]), lam))
    f_end = res.history[-1]["objective"]
    print(f"radisa_tau2_objective {f_end:.4f} (zero-w {f0:.4f})")
    if not f_end < f0:
        fails += 1
    raise SystemExit(fails)


def main_overlap():
    """overlap engine: tau=0 == shard_map bit for bit; tau=2 == async
    at equal tau; codec composition; additive wire accounting."""
    lam = 1.0
    X, y = make_svm_data(120, 42, seed=1)

    fails = 0

    def check_zero(name, a, b):
        nonlocal fails
        d = float(jnp.abs(a - b).max())
        print(f"{name} {d:.3e}")
        if d != 0.0:
            fails += 1

    cases = [
        ("d3ca", D3CAConfig(lam=lam, outer_iters=3, local_steps=12)),
        ("radisa", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("sfk", SFKConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("admm", ADMMConfig(lam=lam, rho=lam, outer_iters=4)),
    ]
    for block_format in ("dense", "sparse"):
        for name, cfg in cases:
            kw = dict(block_format=block_format)
            rs = get_solver(name)(engine="shard_map", **kw).solve(
                "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
            r0 = get_solver(name)(engine="overlap", staleness=0, **kw).solve(
                "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
            check_zero(f"{name}_{block_format}_tau0_w", rs.w, r0.w)
            if rs.alpha is not None:
                check_zero(f"{name}_{block_format}_tau0_alpha",
                           rs.alpha, r0.alpha)
            ra = get_solver(name)(engine="async", staleness=2, **kw).solve(
                "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
            ro = get_solver(name)(engine="overlap", staleness=2, **kw).solve(
                "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
            check_zero(f"{name}_{block_format}_tau2_w", ra.w, ro.w)
            # additive wire accounting: re-timing consumption never
            # changes what goes on the wire
            if (rs.comm_bytes["bytes_per_step"]
                    != ro.comm_bytes["bytes_per_step"]
                    or ra.comm_bytes["bytes_per_step"]
                    != ro.comm_bytes["bytes_per_step"]):
                print(f"{name}_{block_format}_bytes MISMATCH "
                      f"sync={rs.comm_bytes['bytes_per_step']} "
                      f"async={ra.comm_bytes['bytes_per_step']} "
                      f"overlap={ro.comm_bytes['bytes_per_step']}")
                fails += 1

    # the pallas local backend runs inside overlap cells unchanged
    cfg = D3CAConfig(lam=lam, outer_iters=3, local_steps=12)
    rs = get_solver("d3ca")(engine="shard_map",
                            local_backend="pallas").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    r0 = get_solver("d3ca")(engine="overlap", staleness=0,
                            local_backend="pallas").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    check_zero("d3ca_pallas_tau0_w", rs.w, r0.w)

    # codec composition: the EF residual lives with the DISPATCH step,
    # so overlap+int8 must equal async+int8 at equal tau bit for bit
    ra = get_solver("d3ca")(engine="async", staleness=2,
                            compression="int8").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    ro = get_solver("d3ca")(engine="overlap", staleness=2,
                            compression="int8").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    check_zero("d3ca_tau2_int8_w", ra.w, ro.w)

    # hierarchical topology under overlap: pods=2, int8 across pods
    # with error feedback -- still closes the duality gap
    r = get_solver("d3ca")(engine="overlap", staleness=2,
                           topology="pods=2:int8").solve(
        "hinge", X, y, P=Pn, Q=Qn,
        cfg=D3CAConfig(lam=lam, outer_iters=12))
    gap = r.history[-1]["duality_gap"]
    print(f"d3ca_overlap_tau2_hier_gap {gap:.3e}")
    if not gap < 0.5:
        fails += 1
    # ...and hierarchical identity matches the flat overlap run up to
    # f32 reassociation (the two-level psum reorders the sum)
    rf = get_solver("d3ca")(engine="overlap", staleness=2).solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    rh = get_solver("d3ca")(engine="overlap", staleness=2,
                            topology="pods=2").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    d = float(jnp.abs(rf.w - rh.w).max())
    print(f"d3ca_hier_identity_vs_flat_w {d:.3e}")
    if not d < 1e-5:
        fails += 1
    raise SystemExit(fails)


def main_compress():
    """compression=None == identity codec (bit for bit) on the mesh
    engines; exact identity accounting; async composition; EF-int8
    convergence within 2x iterations."""
    lam = 1.0
    X, y = make_svm_data(120, 42, seed=1)

    fails = 0

    def check_zero(name, a, b):
        nonlocal fails
        d = float(jnp.abs(a - b).max())
        print(f"{name} {d:.3e}")
        if d != 0.0:
            fails += 1

    cases = [
        ("d3ca", D3CAConfig(lam=lam, outer_iters=3, local_steps=12)),
        ("radisa", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("sfk", SFKConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("admm", ADMMConfig(lam=lam, rho=lam, outer_iters=4)),
    ]
    for block_format in ("dense", "sparse"):
        for name, cfg in cases:
            rn = get_solver(name)(engine="shard_map",
                                  block_format=block_format).solve(
                "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
            ri = get_solver(name)(engine="shard_map",
                                  block_format=block_format,
                                  compression="identity").solve(
                "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
            check_zero(f"{name}_{block_format}_identity_w", rn.w, ri.w)
            if rn.alpha is not None:
                check_zero(f"{name}_{block_format}_identity_alpha",
                           rn.alpha, ri.alpha)
            # identity accounting invariant: exactly uncompressed bytes
            if (ri.comm_bytes["bytes_per_step"]
                    != rn.comm_bytes["bytes_per_step"]
                    or ri.comm_bytes["bytes_per_step"]
                    != ri.comm_bytes["uncompressed_bytes_per_step"]):
                print(f"{name}_{block_format}_identity_bytes MISMATCH "
                      f"{ri.comm_bytes}")
                fails += 1

    # the pallas local backend runs inside compressed cells unchanged
    cfg = D3CAConfig(lam=lam, outer_iters=3, local_steps=12)
    rn = get_solver("d3ca")(engine="shard_map",
                            local_backend="pallas").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    ri = get_solver("d3ca")(engine="shard_map", local_backend="pallas",
                            compression="identity").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    check_zero("d3ca_pallas_identity_w", rn.w, ri.w)

    # compression composes with the async engine's staleness rings:
    # identity + tau=2 must equal the uncompressed tau=2 run bit for bit
    ra = get_solver("d3ca")(engine="async", staleness=2).solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    rb = get_solver("d3ca")(engine="async", staleness=2,
                            compression="identity").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    check_zero("d3ca_async_tau2_identity_w", ra.w, rb.w)
    # ...and a lossy codec under staleness still closes the gap
    r = get_solver("d3ca")(engine="async", staleness=2,
                           compression="int8").solve(
        "hinge", X, y, P=Pn, Q=Qn,
        cfg=D3CAConfig(lam=lam, outer_iters=12))
    gap = r.history[-1]["duality_gap"]
    print(f"d3ca_async_tau2_int8_gap {gap:.3e}")
    if not gap < 0.5:
        fails += 1

    # EF convergence: int8-compressed D3CA reaches the uncompressed
    # duality gap within 2x the iterations on the small SVM fixture
    T = 8
    gap_ref = get_solver("d3ca")(engine="shard_map").solve(
        "hinge", X, y, P=Pn, Q=Qn,
        cfg=D3CAConfig(lam=lam, outer_iters=T)
    ).history[-1]["duality_gap"]
    r8 = get_solver("d3ca")(engine="shard_map", compression="int8").solve(
        "hinge", X, y, P=Pn, Q=Qn,
        cfg=D3CAConfig(lam=lam, outer_iters=2 * T))
    gap_8 = min(h["duality_gap"] for h in r8.history)
    bytes_ratio = (r8.comm_bytes["uncompressed_bytes_per_step"]
                   / r8.comm_bytes["bytes_per_step"])
    print(f"d3ca_int8_ef_gap {gap_8:.3e} (uncompressed@{T} {gap_ref:.3e}, "
          f"bytes cut {bytes_ratio:.2f}x)")
    if not gap_8 <= gap_ref:
        fails += 1
    if not bytes_ratio >= 3.0:
        print("d3ca_int8_bytes_ratio TOO SMALL")
        fails += 1
    raise SystemExit(fails)


def main():
    lam = 1.0
    # m = 42: P*Q = 8 does not divide it -> exercises the shared padding
    X, y = make_svm_data(120, 42, seed=1)

    fails = 0

    def check(name, a, b, tol=2e-4):
        nonlocal fails
        d = float(jnp.abs(a - b).max())
        print(f"{name} {d:.3e}")
        if not d < tol:
            fails += 1

    cases = [
        ("d3ca", D3CAConfig(lam=lam, outer_iters=3, local_steps=12)),
        ("radisa", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("radisa_avg", RADiSAConfig(lam=lam, gamma=0.03, outer_iters=3,
                                    L=12, variant="avg")),
        ("sfk", SFKConfig(lam=lam, gamma=0.03, outer_iters=3, L=12)),
        ("admm", ADMMConfig(lam=lam, rho=lam, outer_iters=4)),
    ]
    for label, cfg in cases:
        name = "radisa" if label.startswith("radisa") else label
        base = get_solver(name)(engine="simulated", local_backend="ref")
        dist = get_solver(name)(engine="shard_map", local_backend="pallas")
        rb = base.solve("hinge", X, y, P=Pn, Q=Qn, cfg=cfg,
                        record_history=False)
        rd = dist.solve("hinge", X, y, P=Pn, Q=Qn, cfg=cfg,
                        record_history=False)
        check(f"{label}_w", rb.w, rd.w)
        if rb.alpha is not None:
            check(f"{label}_alpha", rb.alpha, rd.alpha)

    # beta step mode across the engine x backend diagonal
    cfg = D3CAConfig(lam=lam, outer_iters=2, local_steps=12,
                     step_mode="beta")
    rb = get_solver("d3ca")(engine="simulated", local_backend="ref").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    rd = get_solver("d3ca")(engine="shard_map",
                            local_backend="pallas").solve(
        "hinge", X, y, P=Pn, Q=Qn, cfg=cfg, record_history=False)
    check("d3ca_beta_w", rb.w, rd.w)

    # regression: silent trailing-column drop is now a loud error
    mesh = make_mesh((Pn, Qn), ("data", "model"))
    try:
        make_radisa_step(get_loss("hinge"), mesh, RADiSAConfig(lam=lam),
                         n=120, n_p=30, m_q=21)
        print("make_radisa_step_mq_check MISSING")
        fails += 1
    except ValueError as e:
        assert "sub-block" in str(e), e
        print("make_radisa_step_mq_check raises ValueError")
    # ... but variant="avg" never sub-splits, so it must still build
    make_radisa_step(get_loss("hinge"), mesh,
                     RADiSAConfig(lam=lam, variant="avg"),
                     n=120, n_p=30, m_q=21)
    print("make_radisa_step_avg_ok")

    raise SystemExit(fails)


if __name__ == "__main__":
    mode = sys.argv[1] if len(sys.argv) > 1 else "sync"
    if mode == "async":
        main_async()
    elif mode == "compress":
        main_compress()
    elif mode == "overlap":
        main_overlap()
    else:
        main()
