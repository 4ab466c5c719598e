"""Communication-overlap sweep: exposed-vs-hidden wire time per tau.

Runs every solver under ``engine="overlap"`` across a staleness grid on
the core-benchmark instance and lands the rows in ``BENCH_core.json``:

  * one cell per (solver, tau):
    ``{solver}/overlap/{backend}/tau{tau}`` with s_per_iter, final
    rel_opt, and the overlap-aware phase split (``comm_exposed_s`` /
    ``comm_hidden_s`` next to ``local_s`` / ``comm_s``);
  * topology cells ``{solver}/overlap/{backend}/tau{tau}/{topo}`` for
    each ``--topologies`` entry (hierarchical intra/inter-pod bytes);
  * an ``overlap_sweep`` block: convergence curves per tau, the
    matched async-engine comparison (same tau, no overlap), and the
    alpha-beta wire-time model fitted on this sweep's own measured
    ``comm_s`` (``fit_link``) with per-cell predicted seconds and
    relative error -- predicted-vs-measured is the figure's payload.

tau = 0 is asserted to reproduce the sync shard_map engine exactly
(max-abs iterate diff == 0); at tau >= 1 the overlap engine's iterates
equal the async engine's (same consumption contract), which is also
asserted.

    PYTHONPATH=src python -m benchmarks.fig_overlap [--quick] \\
        [--taus 0,1,2,4] [--solvers d3ca,radisa,admm] \\
        [--topologies pods=2:int8]

Forces a fake 8-device host platform before jax init (the overlap
engine is a mesh engine).  The payload carries the standard provenance
stamp (git_sha / date / quick).
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
from repro.core.comm_model import Topology  # noqa: E402
from repro.data import make_svm_data  # noqa: E402
from repro.obs import Registry  # noqa: E402

try:
    from .common import (annotate_wire_predictions, emit_csv_row,
                         phase_fields, provenance, timed)
except ImportError:                     # `python benchmarks/fig_overlap.py`
    from common import (annotate_wire_predictions, emit_csv_row,
                        phase_fields, provenance, timed)


def _topo_slug(spec: str) -> str:
    return spec.replace("pods=", "pods").replace(":", "-")


def run_cell(name, cfg, X, y, P, Q, engine, tau, backend, f_star, reps,
             topology=None):
    """One timed solve.  Returns (entry, res)."""
    solver = get_solver(name)(engine=engine, staleness=tau,
                              local_backend=backend, topology=topology)
    prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
    state = prog.step(1, prog.state)              # compile + warm
    if getattr(prog, "donated", False):
        t = None                  # donation invalidates the saved state
    else:
        t = timed(lambda: prog.step(2, state), reps=reps, warmup=0)
    res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=cfg, f_star=f_star,
                       registry=Registry())
    entry = {"rel_opt": res.history[-1]["rel_opt"],
             "iters": res.iters, "staleness": tau, "engine": engine}
    entry.update(phase_fields(res.history))
    if t is None:
        t = entry.get("step_s", 0.0)
    entry["s_per_iter"] = t
    acct = res.comm_bytes
    entry["comm_bytes_per_step"] = acct["bytes_per_step"]
    for tier in ("intra_bytes_per_step", "inter_bytes_per_step"):
        if tier in acct:
            entry[tier] = acct[tier]
    if topology is not None:
        entry["topology"] = res.topology
    return entry, res


def sweep_solver(name, cfg, X, y, P, Q, taus, backend, f_star, reps,
                 topologies):
    """One solver across the staleness grid under overlap + async.
    Returns (cells, curves, samples) where samples feed fit_link."""
    sync = get_solver(name)(engine="shard_map", local_backend=backend)
    w_sync = sync.solve("hinge", X, y, P=P, Q=Q, cfg=cfg,
                        record_history=False).w
    sizes = {"data": P, "model": Q}
    cells, curves, samples = {}, {}, []
    for tau in taus:
        entry, res = run_cell(name, cfg, X, y, P, Q, "overlap", tau,
                              backend, f_star, reps)
        # the engine contracts: tau = 0 IS the sync engine, and the
        # overlap engine consumes reductions exactly like the async one
        w_async = get_solver(name)(
            engine="async", staleness=tau, local_backend=backend).solve(
            "hinge", X, y, P=P, Q=Q, cfg=cfg, record_history=False).w
        diff_async = float(np.abs(np.asarray(res.w)
                                  - np.asarray(w_async)).max())
        entry["max_abs_diff_vs_async"] = diff_async
        assert diff_async == 0.0, (
            f"{name}: overlap(tau={tau}) diverged from async(tau={tau}) "
            f"by {diff_async:.3e}")
        if tau == 0:
            diff = float(np.abs(np.asarray(res.w)
                                - np.asarray(w_sync)).max())
            entry["max_abs_diff_vs_sync"] = diff
            assert diff == 0.0, (
                f"{name}: overlap(staleness=0) diverged from shard_map "
                f"by {diff:.3e}")
        else:
            # the tentpole's win: the async engine pays the same wire
            # but exposes all of it; overlap hides up to tau*local_s
            a_entry, _ = run_cell(name, cfg, X, y, P, Q, "async", tau,
                                  backend, f_star, reps)
            step_s = entry.get("step_s")
            a_step = a_entry.get("step_s")
            if step_s and a_step:
                entry["exposed_share"] = (entry.get("comm_exposed_s", 0.0)
                                          / step_s)
                entry["async_comm_share"] = (a_entry.get("comm_s", 0.0)
                                             / a_step)
        if "comm_s" in entry:
            samples.append((res.comm_bytes, sizes, entry["comm_s"],
                            f"{name}/overlap/{backend}/tau{tau}", None))
        cells[f"{name}/overlap/{backend}/tau{tau}"] = entry
        curves[str(tau)] = [h["rel_opt"] for h in res.history]
        emit_csv_row(f"fig_overlap/{name}/tau{tau}",
                     entry["s_per_iter"] * 1e6,
                     f"rel_opt={entry['rel_opt']:.4f}")
        for topo in topologies:
            tau_t = tau if tau else max(taus)
            if tau != tau_t:
                continue          # one topology row per solver, max tau
            t_entry, t_res = run_cell(name, cfg, X, y, P, Q, "overlap",
                                      tau, backend, f_star, reps,
                                      topology=topo)
            key = f"{name}/overlap/{backend}/tau{tau}/{_topo_slug(topo)}"
            if "comm_s" in t_entry:
                samples.append((t_res.comm_bytes, sizes, t_entry["comm_s"],
                                key, Topology.from_spec(topo)))
            cells[key] = t_entry
            emit_csv_row(f"fig_overlap/{name}/tau{tau}/{_topo_slug(topo)}",
                         t_entry["s_per_iter"] * 1e6,
                         f"rel_opt={t_entry['rel_opt']:.4f}")
    return cells, curves, samples


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized instances")
    ap.add_argument("--taus", default="0,1,2,4",
                    help="comma-separated staleness grid")
    ap.add_argument("--solvers", default="d3ca,radisa,admm")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--topologies", default="pods=2:int8",
                    help="comma-separated hierarchical topology specs "
                         "(empty string skips the topology cells)")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_core.json"))
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    taus = [int(t) for t in args.taus.split(",") if t != ""]
    bad = [t for t in taus if t < 0]
    if bad:
        ap.error(f"--taus contains negative staleness values {bad}; "
                 "tau must be >= 0")
    topologies = [t.strip() for t in args.topologies.split(",") if t.strip()]

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

    if os.path.exists(args.out):
        with open(args.out) as fh:
            payload = json.load(fh)
    else:
        payload = {"cells": {}, "ratios": {}}
    payload.setdefault("cells", {})
    payload["overlap_sweep"] = {"taus": taus, "n": n, "m": m, "P": P,
                                "Q": Q, "lam": lam, "iters": iters,
                                "backend": args.backend,
                                "topologies": topologies, "curves": {}}
    payload["provenance"] = provenance(args.quick)

    all_samples = []
    for name in args.solvers.split(","):
        cells, curves, samples = sweep_solver(
            name, configs[name], X, y, P, Q, taus, args.backend, f_star,
            args.reps, topologies)
        payload["cells"].update(cells)
        payload["overlap_sweep"]["curves"][name] = curves
        all_samples.extend(samples)

    if all_samples:
        payload["overlap_sweep"]["wire_model"] = annotate_wire_predictions(
            payload["cells"], all_samples)

    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"[fig_overlap] wrote {args.out} "
          f"({len(taus)} taus x {len(args.solvers.split(','))} solvers)")
    return payload


if __name__ == "__main__":
    main()
