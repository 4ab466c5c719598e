"""Core solver benchmark: outer-step throughput of the unified solver API
under every (engine, local_backend) pair.

Forces a fake 8-device host platform (before jax init) so the shard_map
engine runs its real collectives on CPU.  On CPU the pallas backend runs
in interpret mode -- those numbers validate plumbing and track the perf
trajectory, not TPU throughput (the dry-run/roofline path is the TPU
performance story).

    PYTHONPATH=src python -m benchmarks.core_bench [--quick]

Emits ``BENCH_core.json`` (repo root by default): seconds per outer
iteration per (solver, engine, backend[, sparse]) cell plus the
headline ratios -- ref vs pallas per engine, simulated vs shard_map per
backend, and sparse vs dense per (engine, backend) on the low-density
instance.  The payload carries a provenance stamp (git_sha / date /
quick) that ``benchmarks.check_regression`` requires before gating.
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

import time  # noqa: E402

import jax  # noqa: E402

from repro.core import (D3CAConfig, RADiSAConfig, ADMMConfig,  # noqa: E402
                        get_solver, objective, serial_sdca)
from repro.data import make_sparse_svm_data, make_svm_data  # noqa: E402
from repro.obs import Registry, Tracer  # noqa: E402

try:
    from .common import emit_csv_row, phase_fields, provenance, timed
except ImportError:                       # `python benchmarks/core_bench.py`
    from common import emit_csv_row, phase_fields, provenance, timed


def bench_combo(name, cfg, X, y, P, Q, engine, backend, f_star, reps,
                block_format="dense", compression=None, staleness=0):
    solver = get_solver(name)(engine=engine, local_backend=backend,
                              block_format=block_format,
                              compression=compression, staleness=staleness)
    prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
    state = prog.step(1, prog.state)          # compile + warm
    t = timed(lambda: prog.step(2, state), reps=reps, warmup=0)
    # a short solve for a correctness anchor on the same combo; the
    # registry switches it to the timed drive path, so its history also
    # carries the per-phase attribution (step_s / local_s / comm_s /
    # host_s means land in the cell)
    res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=cfg, f_star=f_star,
                       record_history=True, registry=Registry())
    cell = {"s_per_iter": t, "rel_opt": res.history[-1]["rel_opt"],
            "iters": res.iters,
            "comm_bytes_per_step": res.comm_bytes["bytes_per_step"]}
    cell.update(phase_fields(res.history))
    return cell


def trace_overhead(name, cfg, X, y, P, Q, iters, reps):
    """Per-iter drive-loop cost with tracing on vs off, same warm program.

    This measures exactly what an enabled Tracer adds to the hot loop
    (span bookkeeping + the per-step block_until_ready the timed path
    needs) without the one-time phase calibration, which amortizes to
    zero over a long solve.  min-over-reps on both sides to shed
    scheduler noise.

    The tracer's cost is a fixed few microseconds per outer iteration,
    so the *fraction* depends on step duration; the probe uses a
    realistic inner-epoch count rather than the quick grid's micro-step
    (on a 0.1 ms step even a perfect tracer misses a 3% budget).

    The same probe also measures the FlightRecorder (the ring-buffer
    tracer the long-running services leave on): its capacity is set
    BELOW the span count of the run so every recorded iteration pays
    the drop-oldest path -- the steady state of a service that has been
    up for hours."""
    from repro.core.engines import drive
    from repro.obs import FlightRecorder

    cfg = type(cfg)(lam=cfg.lam, outer_iters=cfg.outer_iters,
                    local_steps=max(1024, cfg.local_steps))
    solver = get_solver(name)(engine="simulated", local_backend="ref")
    prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
    jax.block_until_ready(prog.step(1, prog.state))      # compile + warm

    def run(tracer):
        t0 = time.perf_counter()
        state, _, _ = drive(prog, iters, tracer=tracer)
        jax.block_until_ready(state)
        return (time.perf_counter() - t0) / iters

    run(Tracer())                                        # warm both paths
    untraced = min(run(None) for _ in range(reps))
    traced = min(run(Tracer()) for _ in range(reps))
    # capacity < spans per run (2/iter: repro.iter + repro.step) => the whole
    # run exercises the at-capacity drop path
    recorded = min(run(FlightRecorder(capacity=max(2, iters)))
                   for _ in range(reps))
    return {"untraced_s_per_iter": untraced, "traced_s_per_iter": traced,
            "overhead_frac": traced / untraced - 1.0,
            "recorder_s_per_iter": recorded,
            "recorder_overhead_frac": recorded / untraced - 1.0}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized instances")
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_core.json"))
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--trace-out", default=None, metavar="TRACE.json",
                    help="also run one traced d3ca/simulated/ref solve "
                         "and write its Chrome-trace JSON here (the CI "
                         "bench job uploads it as an artifact)")
    ap.add_argument("--max-trace-overhead", type=float, default=0.03,
                    help="fail when an enabled tracer slows s_per_iter "
                         "by more than this fraction")
    args = ap.parse_args(argv)

    P, Q = 4, 2
    n, m = (256, 96) if args.quick else (768, 256)
    inner = 32 if args.quick else 96
    iters = 3 if args.quick else 5
    density = 0.05
    X, y = make_svm_data(n, m, seed=0)
    # the sparse grid runs on a low-density instance (weak-scaling
    # regime); dense np array in, partitioned into ELL cells by the
    # block_format knob
    Xs, ys = make_sparse_svm_data(n, m, density=density, seed=0)
    lam = 1e-1
    w_ref, _ = serial_sdca("hinge", X, y, lam=lam, epochs=100)
    f_star = float(objective("hinge", X, y, w_ref, lam))
    ws_ref, _ = serial_sdca("hinge", Xs, ys, lam=lam, epochs=100)
    fs_star = float(objective("hinge", Xs, ys, ws_ref, lam))

    configs = {
        "d3ca": D3CAConfig(lam=lam, outer_iters=iters, local_steps=inner),
        "radisa": RADiSAConfig(lam=lam, gamma=0.05, outer_iters=iters,
                               L=inner),
        "admm": ADMMConfig(lam=lam, rho=lam, outer_iters=iters),
    }
    out = {"n": n, "m": m, "P": P, "Q": Q, "lam": lam, "inner": inner,
           "sparse_density": density,
           "note": "pallas numbers are interpret-mode on CPU unless run "
                   "on a TPU host",
           "provenance": provenance(args.quick),
           "cells": {}, "ratios": {}}

    # the overlap engine rides the grid at a fixed tau (its own tau
    # sweep lives in fig_overlap); tau > 0 hides comm behind local solve
    overlap_tau = 2
    for name, cfg in configs.items():
        backends = ("ref",) if name == "admm" else ("ref", "pallas")
        for engine in ("simulated", "shard_map", "overlap"):
            tau = overlap_tau if engine == "overlap" else 0
            for backend in backends:
                key = f"{name}/{engine}/{backend}"
                cell = bench_combo(name, cfg, X, y, P, Q, engine, backend,
                                   f_star, args.reps, staleness=tau)
                out["cells"][key] = cell
                emit_csv_row(f"core/{key}", cell["s_per_iter"] * 1e6,
                             f"rel_opt={cell['rel_opt']:.4f}")
                skey = f"{key}/sparse"
                scell = bench_combo(name, cfg, Xs, ys, P, Q, engine,
                                    backend, fs_star, args.reps,
                                    block_format="sparse", staleness=tau)
                out["cells"][skey] = scell
                emit_csv_row(f"core/{skey}", scell["s_per_iter"] * 1e6,
                             f"rel_opt={scell['rel_opt']:.4f}")

    cells = out["cells"]
    for name in configs:
        for engine in ("simulated", "shard_map", "overlap"):
            r = cells.get(f"{name}/{engine}/ref")
            p = cells.get(f"{name}/{engine}/pallas")
            if r and p:
                out["ratios"][f"{name}/{engine}/pallas_over_ref"] = (
                    p["s_per_iter"] / r["s_per_iter"])
        for backend in ("ref", "pallas"):
            s = cells.get(f"{name}/simulated/{backend}")
            d = cells.get(f"{name}/shard_map/{backend}")
            if s and d:
                out["ratios"][f"{name}/{backend}/shard_map_over_simulated"] \
                    = (d["s_per_iter"] / s["s_per_iter"])
            o = cells.get(f"{name}/overlap/{backend}")
            if d and o:
                out["ratios"][f"{name}/{backend}/overlap_over_shard_map"] \
                    = (o["s_per_iter"] / d["s_per_iter"])
            for engine in ("simulated", "shard_map", "overlap"):
                dn = cells.get(f"{name}/{engine}/{backend}")
                sp = cells.get(f"{name}/{engine}/{backend}/sparse")
                if dn and sp:
                    out["ratios"][
                        f"{name}/{engine}/{backend}/sparse_over_dense"] = (
                        sp["s_per_iter"] / dn["s_per_iter"])

    # tracing-overhead gate: an enabled tracer must stay within
    # --max-trace-overhead of the untraced drive loop (s_per_iter).  The
    # absolute floor absorbs timer granularity on sub-millisecond iters.
    ov = trace_overhead("d3ca", configs["d3ca"], X, y, P, Q,
                        iters=max(10, 4 * iters), reps=max(3, args.reps))
    out["trace_overhead"] = ov
    print(f"[core_bench] trace overhead: "
          f"{ov['untraced_s_per_iter'] * 1e3:.3f} -> "
          f"{ov['traced_s_per_iter'] * 1e3:.3f} ms/iter "
          f"({100 * ov['overhead_frac']:+.2f}%); recorder "
          f"{ov['recorder_s_per_iter'] * 1e3:.3f} ms/iter "
          f"({100 * ov['recorder_overhead_frac']:+.2f}%)")
    budget = (ov["untraced_s_per_iter"] * (1.0 + args.max_trace_overhead)
              + 5e-4)
    assert ov["traced_s_per_iter"] <= budget, (
        f"enabled tracer adds {100 * ov['overhead_frac']:.1f}% per iter "
        f"(> {100 * args.max_trace_overhead:.0f}% budget)")
    assert ov["recorder_s_per_iter"] <= budget, (
        f"flight recorder adds "
        f"{100 * ov['recorder_overhead_frac']:.1f}% per iter at capacity "
        f"(> {100 * args.max_trace_overhead:.0f}% budget)")

    if args.trace_out:
        tracer = Tracer()
        solver = get_solver("d3ca")(engine="simulated", local_backend="ref")
        solver.solve("hinge", X, y, P=P, Q=Q, cfg=configs["d3ca"],
                     f_star=f_star, tracer=tracer)
        tracer.write_chrome_trace(args.trace_out)
        print(f"[core_bench] trace: {len(tracer.events)} events -> "
              f"{args.trace_out}")

    with open(args.out, "w") as fh:
        json.dump(out, fh, indent=1)
    print(f"[core_bench] wrote {args.out}")
    return out


if __name__ == "__main__":
    main()
