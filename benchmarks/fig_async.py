"""Staleness-vs-convergence sweep for the async engine (Engine API v2).

Runs every solver under ``engine="async"`` across a staleness grid
(default tau in {0, 1, 2, 4}) on the same instance the core benchmark
uses, and lands the rows in ``BENCH_core.json``:

  * one cell per (solver, tau): ``{solver}/async/{backend}/tau{tau}``
    with s_per_iter + final rel_opt (so the CI regression gate sees the
    async engine the same way it sees every other cell);
  * an ``async_sweep`` block with the full convergence trajectories
    (rel_opt per outer iteration per tau) -- the figure's payload.

tau = 0 is asserted to reproduce the sync shard_map engine exactly
(max-abs iterate diff == 0), which is the API's staleness contract.

    PYTHONPATH=src python -m benchmarks.fig_async [--quick] \\
        [--taus 0,1,2,4] [--solvers d3ca,radisa,admm]

Forces a fake 8-device host platform before jax init (the async engine
is a mesh engine).  The payload carries the standard provenance stamp
(git_sha / date / quick).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, os.path.join(ROOT, "src"))

try:
    from .common import force_host_devices
except ImportError:                       # run as a script
    from common import force_host_devices

force_host_devices(8)

import numpy as np  # noqa: E402

from repro.core import (ADMMConfig, D3CAConfig, RADiSAConfig,  # noqa: E402
                        get_solver, objective, serial_sdca)
from repro.data import make_svm_data  # noqa: E402

try:
    from .common import (annotate_wire_predictions, emit_csv_row,
                         phase_fields, provenance, timed)
except ImportError:                       # `python benchmarks/fig_async.py`
    from common import (annotate_wire_predictions, emit_csv_row,
                        phase_fields, provenance, timed)


def sweep_solver(name, cfg, X, y, P, Q, taus, backend, f_star, reps):
    """One solver across the staleness grid.  Returns (cells, curves,
    samples) -- samples feed the wire-time model fit."""
    sync = get_solver(name)(engine="shard_map", local_backend=backend)
    w_sync = sync.solve("hinge", X, y, P=P, Q=Q, cfg=cfg,
                        record_history=False).w
    cells, curves, samples = {}, {}, []
    for tau in taus:
        solver = get_solver(name)(engine="async", staleness=tau,
                                  local_backend=backend)
        prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
        state = prog.step(1, prog.state)          # compile + warm
        t = timed(lambda: prog.step(2, state), reps=reps, warmup=0)
        from repro.obs import Registry
        res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=cfg, f_star=f_star,
                           registry=Registry())
        entry = {"s_per_iter": t,
                 "rel_opt": res.history[-1]["rel_opt"],
                 "iters": res.iters, "staleness": tau}
        entry.update(phase_fields(res.history))
        # per-collective bytes-on-wire counters (the staleness model
        # launches every collective every step, so tau does not change
        # the wire cost -- which is exactly what makes async and
        # compressed runs comparable on the same axis)
        acct = res.comm_bytes
        entry["comm_bytes_per_step"] = acct["bytes_per_step"]
        entry["comm_bytes_by_collective"] = {
            cname: c["bytes_per_step"]
            for cname, c in acct["collectives"].items()}
        if "duality_gap" in res.history[-1]:
            entry["duality_gap"] = res.history[-1]["duality_gap"]
        if tau == 0:
            # the API contract: tau = 0 IS the sync engine
            diff = float(np.abs(np.asarray(res.w) - np.asarray(w_sync)).max())
            entry["max_abs_diff_vs_sync"] = diff
            assert diff <= 1e-8, (
                f"{name}: async(staleness=0) diverged from shard_map "
                f"by {diff:.3e} (> 1e-8)")
        key = f"{name}/async/{backend}/tau{tau}"
        if "comm_s" in entry:
            samples.append((acct, {"data": P, "model": Q},
                            entry["comm_s"], key, None))
        cells[key] = entry
        curves[str(tau)] = [h["rel_opt"] for h in res.history]
        emit_csv_row(f"fig_async/{name}/tau{tau}", t * 1e6,
                     f"rel_opt={entry['rel_opt']:.4f}")
    return cells, curves, samples


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized instances")
    ap.add_argument("--taus", default="0,1,2,4",
                    help="comma-separated staleness grid")
    ap.add_argument("--solvers", default="d3ca,radisa,admm")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_core.json"))
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    taus = [int(t) for t in args.taus.split(",") if t != ""]
    bad = [t for t in taus if t < 0]
    if bad:
        ap.error(f"--taus contains negative staleness values {bad}; "
                 "tau must be >= 0")

    P, Q = 4, 2
    n, m = (256, 96) if args.quick else (768, 256)
    inner = 32 if args.quick else 96
    iters = 6 if args.quick else 12
    lam = 1e-1
    X, y = make_svm_data(n, m, seed=0)
    w_ref, _ = serial_sdca("hinge", X, y, lam=lam, epochs=100)
    f_star = float(objective("hinge", X, y, w_ref, lam))

    configs = {
        "d3ca": D3CAConfig(lam=lam, outer_iters=iters, local_steps=inner),
        "radisa": RADiSAConfig(lam=lam, gamma=0.05, outer_iters=iters,
                               L=inner),
        "admm": ADMMConfig(lam=lam, rho=lam, outer_iters=iters),
    }

    # land the rows in BENCH_core.json next to the core grid (fresh
    # payload when core_bench has not run in this checkout)
    if os.path.exists(args.out):
        with open(args.out) as fh:
            payload = json.load(fh)
    else:
        payload = {"cells": {}, "ratios": {}}
    payload.setdefault("cells", {})
    payload["async_sweep"] = {"taus": taus, "n": n, "m": m, "P": P, "Q": Q,
                              "lam": lam, "iters": iters,
                              "backend": args.backend, "curves": {}}
    payload["provenance"] = provenance(args.quick)

    all_samples = []
    for name in args.solvers.split(","):
        cells, curves, samples = sweep_solver(
            name, configs[name], X, y, P, Q, taus, args.backend, f_star,
            args.reps)
        payload["cells"].update(cells)
        payload["async_sweep"]["curves"][name] = curves
        all_samples.extend(samples)

    if all_samples:
        payload["async_sweep"]["wire_model"] = annotate_wire_predictions(
            payload["cells"], all_samples)

    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"[fig_async] wrote {args.out} "
          f"({len(taus)} taus x {len(args.solvers.split(','))} solvers)")
    return payload


if __name__ == "__main__":
    main()
