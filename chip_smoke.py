#!/usr/bin/env python3
"""Smoke run of the paper's solvers on a TPU, through the solver API.

    python3 chip_smoke.py              # one chip, one process
    python3 chip_smoke.py --chips 4    # the mesh phase on a 2x2 host

One chip (the paper's own sizes, data generated from ``--seed``):

  d3ca_dense    D3CA, dense hinge, paper Part 1 "4x2": 8,000 x 6,000 in
                2,000 x 3,000 blocks, lam 1e-2, simulated engine, Pallas
                local solver, 5 outer iterations.
  radisa_dense  RADiSA on the same data, gamma = 1 / (mean squared norm of
                a row's sub-block), so that one step moves a row's
                margin by about its loss gradient.  The same phase runs
                the SVRG kernel alone on one cell against a plain scan,
                anchored at the solve's w, and counts the steps whose
                loss-gradient difference was nonzero.
  d3ca_sparse   D3CA on padded-ELL blocks of a real-sim-shaped instance:
                72,309 x 20,958 at density 2.4e-3, 4x2 grid, lam 1e-4.

Each phase checks that the jitted step holds a compiled Pallas kernel
(``tpu_custom_call``), that the objective is finite and falls below
both F(0) = 1 (hinge at w = 0) and its first iterate's, for D3CA that
the duality gap falls, and that ``w`` agrees with
``local_backend="ref"`` solved on the same chip at float32 matmul
precision: relative L2 difference at most ``REF_TOL``.

``--chips 4`` runs only the mesh phase: Part-1 blocks on a 2x2
(data, model) mesh, 4,000 x 6,000, D3CA and RADiSA with the Pallas
local solver.  ``engine="shard_map"`` is compared with
``engine="simulated"`` on one chip (``MESH_TOL``), ``engine="overlap"``
at staleness 2 with ``engine="async"`` at staleness 2 (``OVERLAP_TOL``;
the overlap engine donates its state off the CPU), the synchronous
engines' objectives must fall below F(0) and their first iterate's, and
the state of a stepped mesh program must sit on 4 distinct devices.

The last line of standard output is ``{"ok": true, "device": {...}}``.
Without a TPU, or when any check fails, the script exits non-zero and
prints no such line.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: relative L2 difference allowed between the Pallas solve (default
#: matmul precision) and the float32 ref solve on the same chip; the
#: sound readings on a v5e are at most 3.3e-7, so this is a 30x margin
REF_TOL = 1e-5
#: shard_map mesh vs simulated grid, same backend and data (sound
#: readings at most 3.8e-7)
MESH_TOL = 1e-5
#: overlap engine vs async engine at equal staleness
OVERLAP_TOL = 1e-6
ITERS = 5
#: hinge objective at w = 0: mean(max(0, 1 - y * 0)) + 0
F_ZERO = 1.0


def rel_l2(a, b) -> float:
    import numpy as np
    a = np.asarray(a, np.float64)
    b = np.asarray(b, np.float64)
    return float(np.linalg.norm(a - b) / max(np.linalg.norm(b), 1e-30))


def check(ok: bool, what: str):
    if not ok:
        raise AssertionError(what)


def radisa_gamma(X, P: int, Q: int) -> float:
    """RADiSA step constant for ``X`` on a P x Q grid: the inverse of the
    mean squared norm of a row's sub-block (m / (P Q) columns), so that
    the first inner step moves a row's margin by about its loss
    gradient.  A fixed gamma overshoots as the sub-block widens."""
    import numpy as np
    row_sq = float(np.mean(np.einsum("ij,ij->i", X, X)))
    return P * Q / row_sq


def hinge_grad(z, y):
    import jax.numpy as jnp
    return jnp.where(y * z < 1.0, -y, 0.0)


def svrg_kernel_check(X, y, w, P, Q, cfg, seed: int) -> dict:
    """The Pallas SVRG kernel alone on cell (0, 0), sub-block 0, at the
    second outer iteration's step size, anchored at ``w``, against a
    plain float32 scan of Algorithm 3's inner loop.  The scan counts the
    steps whose loss-gradient difference is nonzero: only those use the
    sampled row's margin, so a count of 0 would leave the kernel's
    row-dependent part untested."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.svrg import svrg_inner_pallas
    n, m = X.shape
    n_p, m_sub = n // P, m // (P * Q)
    w64 = np.asarray(w, np.float64)
    z_all = X.astype(np.float64) @ w64
    g_all = np.where(y * z_all < 1.0, -y, 0.0)
    mu = (g_all @ X.astype(np.float64)) / n + cfg.lam * w64
    x_sub = jnp.asarray(X[:n_p, :m_sub])
    y_c = jnp.asarray(y[:n_p])
    mask = jnp.ones((n_p,), jnp.float32)
    z = jnp.asarray(z_all[:n_p], jnp.float32)
    wa = jnp.asarray(w64[:m_sub], jnp.float32)
    mu_sub = jnp.asarray(mu[:m_sub], jnp.float32)
    idx = jax.random.randint(jax.random.PRNGKey(seed), (n_p,), 0, n_p)
    eta = float(cfg.eta(2.0))
    w_kernel = svrg_inner_pallas(x_sub, y_c, mask, z, wa, mu_sub, idx,
                                 lam=cfg.lam, eta=eta)

    @jax.jit
    def plain(x_sub, y_c, z, wa, mu_sub, idx):
        def body(carry, j):
            w, fires = carry
            zj = z[j] + jnp.dot(x_sub[j], w - wa, precision="highest")
            d = hinge_grad(zj, y_c[j]) - hinge_grad(z[j], y_c[j])
            g = d * x_sub[j] + mu_sub + cfg.lam * (w - wa)
            return (w - eta * g, fires + (d != 0)), None
        (w_end, fires), _ = jax.lax.scan(body, (wa, jnp.int32(0)), idx)
        return w_end, fires

    w_plain, fires = plain(x_sub, y_c, z, wa, mu_sub, idx)
    return {"steps": n_p, "grad_diff_nonzero_steps": int(fires),
            "w_rel_l2_vs_plain": rel_l2(w_kernel, w_plain),
            "w_moved_rel_l2": rel_l2(w_plain, wa)}


def solve(name, X, y, P, Q, cfg, *, backend="pallas", block_format="dense",
          engine="simulated", staleness=0):
    from repro.core import get_solver
    solver = get_solver(name)(engine=engine, local_backend=backend,
                              block_format=block_format, staleness=staleness)
    return solver, solver.solve("hinge", X, y, P=P, Q=Q, cfg=cfg)


def first_steps(solver, X, y, P, Q, cfg):
    """Build ``solver``'s program on this data, check that its step holds
    a compiled Pallas kernel, and run two steps.  Returns (compile
    seconds, seconds of one compiled step)."""
    import jax
    prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
    check("tpu_custom_call" in jax.jit(prog.step).lower(1, prog.state)
          .as_text(),
          "the step holds no tpu_custom_call: the kernel did not compile")
    t0 = time.perf_counter()
    state = jax.block_until_ready(prog.step(1, prog.state))
    t1 = time.perf_counter()
    jax.block_until_ready(prog.step(2, state))
    t2 = time.perf_counter()
    return (t1 - t0) - (t2 - t1), t2 - t1


def phase(tag, name, X, y, P, Q, cfg, *, block_format="dense",
          gap_falls=False, seed=0):
    """One solver at full size: Pallas vs the float32 ref on this chip."""
    import jax
    import numpy as np
    from repro.core import get_solver
    # program_cache: the solve reuses the step compiled by first_steps
    solver = get_solver(name)(engine="simulated", local_backend="pallas",
                              block_format=block_format, program_cache=True)
    compile_s, step_s = first_steps(solver, X, y, P, Q, cfg)
    res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=cfg)
    with jax.default_matmul_precision("highest"):
        _, ref = solve(name, X, y, P, Q, cfg, backend="ref",
                       block_format=block_format)
    objs = [h["objective"] for h in res.history]
    gaps = [h.get("duality_gap") for h in res.history]
    diff = rel_l2(res.w, ref.w)
    kernel = (svrg_kernel_check(X, y, res.w, P, Q, cfg, seed)
              if name == "radisa" else None)
    print(json.dumps({
        "phase": tag, "solver": name, "block_format": block_format,
        "shape": list(X.shape), "grid": [P, Q], "compile_s": compile_s,
        "step_s": step_s,
        "iters": res.iters, "elapsed_s": [h["time_s"] for h in res.history],
        "objective": objs[-1], "ref_objective": ref.history[-1]["objective"],
        "duality_gap": gaps, "w_rel_l2_vs_ref": diff,
        "w_max_abs_diff_vs_ref": float(np.max(np.abs(
            np.asarray(res.w) - np.asarray(ref.w)))),
        **({"svrg_kernel": kernel} if kernel else {}),
    }), flush=True)
    check(res.iters == cfg.outer_iters, f"{tag}: ran {res.iters} iterations")
    check(bool(np.all(np.isfinite(objs))), f"{tag}: objective not finite")
    check(bool(np.all(np.isfinite(np.asarray(res.w)))), f"{tag}: w not finite")
    check(objs[-1] < min(F_ZERO, objs[0]),
          f"{tag}: objective {objs} does not fall below F(0) = {F_ZERO} "
          "and its first iterate")
    if gap_falls:
        check(gaps[-1] < gaps[0], f"{tag}: duality gap did not fall {gaps}")
    check(diff <= REF_TOL, f"{tag}: w differs from ref by {diff} > {REF_TOL}")
    if kernel:
        check(kernel["grad_diff_nonzero_steps"] > 0,
              f"{tag}: no SVRG step used its row's margin")
        check(kernel["w_rel_l2_vs_plain"] <= REF_TOL,
              f"{tag}: the SVRG kernel differs from the plain scan by "
              f"{kernel['w_rel_l2_vs_plain']} > {REF_TOL}")


def one_chip(seed: int):
    from repro.configs.svm_paper import PART1, REAL_DATASETS
    from repro.core import D3CAConfig, RADiSAConfig
    from repro.data import make_sparse_svm_csr, make_svm_data
    ex = next(e for e in PART1 if e.name == "4x2")
    X, y = make_svm_data(ex.n, ex.m, seed=seed)
    phase("d3ca_dense", "d3ca", X, y, ex.P, ex.Q,
          D3CAConfig(lam=ex.lam, outer_iters=ITERS), gap_falls=True)
    phase("radisa_dense", "radisa", X, y, ex.P, ex.Q,
          RADiSAConfig(lam=ex.lam, gamma=radisa_gamma(X, ex.P, ex.Q),
                       outer_iters=ITERS), seed=seed)
    rs = REAL_DATASETS["realsim"]
    Xs, ys = make_sparse_svm_csr(rs["n"], rs["m"], density=rs["density"],
                                 seed=seed)
    phase("d3ca_sparse", "d3ca", Xs, ys, 4, 2,
          D3CAConfig(lam=rs["lam"], outer_iters=ITERS),
          block_format="sparse", gap_falls=True)


def mesh_spans_devices(solver, X, y, P, Q, cfg) -> int:
    """Distinct devices holding the state after one mesh step."""
    import jax
    prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
    state = jax.block_until_ready(prog.step(1, prog.state))
    return len({s.device for leaf in jax.tree_util.tree_leaves(state)
                for s in leaf.addressable_shards})


def four_chips(seed: int):
    import jax
    import numpy as np
    from repro.configs.svm_paper import PART1
    from repro.core import D3CAConfig, RADiSAConfig
    from repro.data import make_svm_data
    ex = next(e for e in PART1 if e.name == "4x2")
    P = Q = 2
    X, y = make_svm_data(P * ex.block_n, Q * ex.block_m, seed=seed)
    cfgs = {"d3ca": D3CAConfig(lam=ex.lam, outer_iters=ITERS),
            "radisa": RADiSAConfig(lam=ex.lam, gamma=radisa_gamma(X, P, Q),
                                   outer_iters=ITERS)}
    for name, cfg in cfgs.items():
        mesh_solver, mesh = solve(name, X, y, P, Q, cfg, engine="shard_map")
        _, grid = solve(name, X, y, P, Q, cfg)
        _, asy = solve(name, X, y, P, Q, cfg, engine="async", staleness=2)
        ovl_solver, ovl = solve(name, X, y, P, Q, cfg, engine="overlap",
                                staleness=2)
        devices = mesh_spans_devices(mesh_solver, X, y, P, Q, cfg)
        ovl_prog = ovl_solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
        d_mesh = rel_l2(mesh.w, grid.w)
        d_ovl = rel_l2(ovl.w, asy.w)
        print(json.dumps({
            "phase": f"{name}_mesh", "shape": list(X.shape), "grid": [P, Q],
            "objective": {
                eng: [h["objective"] for h in res.history]
                for eng, res in (("shard_map", mesh), ("simulated", grid),
                                 ("async_tau2", asy), ("overlap_tau2", ovl))},
            "w_rel_l2_shard_map_vs_simulated": d_mesh,
            "w_rel_l2_overlap_vs_async": d_ovl,
            "overlap_vs_async_bit_identical": bool(np.array_equal(
                np.asarray(ovl.w), np.asarray(asy.w))),
            "overlap_donates": ovl_prog.donated,
            "state_devices": devices,
        }), flush=True)
        for res in (mesh, grid, asy, ovl):
            check(bool(np.all(np.isfinite(np.asarray(res.w)))),
                  f"{name}: w not finite under {res.engine}")
        for res in (mesh, grid):
            objs = [h["objective"] for h in res.history]
            check(objs[-1] < min(F_ZERO, objs[0]),
                  f"{name}: objective {objs} under {res.engine} does not "
                  f"fall below F(0) = {F_ZERO} and its first iterate")
        check(d_mesh <= MESH_TOL,
              f"{name}: shard_map vs simulated {d_mesh} > {MESH_TOL}")
        check(d_ovl <= OVERLAP_TOL,
              f"{name}: overlap vs async {d_ovl} > {OVERLAP_TOL}")
        check(ovl_prog.donated, f"{name}: the overlap engine did not donate")
        check(devices == jax.device_count() == 4,
              f"{name}: state on {devices} devices, not 4")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(json.dumps({"device": device, "compile_cache": cache}), flush=True)
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU (platform {device['platform']!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {device['count']}", file=sys.stderr)
        return 2

    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"total_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
