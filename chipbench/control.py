#!/usr/bin/env python3
"""Readings that set a cell's limits: the program's sound runs, the
precision control and a planted fault, seed by seed, in one process.

    python3 chipbench/control.py --workload synth_realsim_4x2.cold \\
        --program-seeds 101,102,... --control-seeds 201,202,203

For each program seed: the data, one warm solve, then ``--solves``
solves through ``Solver.solve`` (the timed path, at the cell's size),
checked as a benchmark run checks them.  For each control seed: the
plain reference D3CA (:func:`chipbench.reference.plain_d3ca`) put in the
program's place, every array in bfloat16 — the precision below the
float32 the configuration states — checked the same way.  Prints one
JSON line per seed with every number compared.  For each fault seed:
the program with ``--fault`` planted (:mod:`chipbench.faults`), one
solve, checked the same way.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def reference_solver(dtype_name: str):
    """A ``solver_factory`` for :func:`chipbench.harness.run_cell` that
    puts the plain reference D3CA, in ``dtype_name``, in the program's
    place."""
    def factory(cell, problem, seed):
        import jax.numpy as jnp
        from chipbench.problem import KEY_SEED_MOD
        from chipbench.reference import PlainD3CA
        cfg = cell.config
        P, Q = cfg["grid"]
        plain = PlainD3CA(problem, P=P, Q=Q, dtype=getattr(jnp, dtype_name))

        def solve(request, start=None):
            if start is not None:
                raise ValueError("the plain reference starts cold only")
            w, alpha, gap, iters, converged = plain.solve(
                lam=request["lam"], target=request["gap_target"],
                max_iters=cfg["outer_iters"],
                seed=int(seed) % KEY_SEED_MOD)
            return iters, converged, gap, w, alpha

        return solve
    return factory


def readings(cell, seed: int, solves: int, factory) -> dict:
    """Build the system from ``factory``, warm it up, run ``solves``
    solves of the mix's first request, and check them."""
    from chipbench.harness import check, requests, solve_once
    from chipbench.problem import make_problem
    problem = make_problem(cell.config, seed)
    solve = factory(cell, problem, seed)
    request = requests(cell, seed)[0]
    t0 = time.perf_counter()
    solve(request)
    warm_s = time.perf_counter() - t0
    records = [solve_once(solve, i, request, None) for i in range(solves)]
    answers = {r.index: (np.asarray(r.w), np.asarray(r.alpha))
               for r in records}
    numbers, certs = check(cell, problem, records, answers)
    return {"seed": seed, "warm_s": warm_s,
            "certified_gap": [c["gap"] for c in certs.values()],
            "solve_s": [r.end - r.begin for r in records],
            "iters": [r.iters for r in records],
            "reported_gap": [r.gap for r in records],
            "numbers": {k: v["value"] for k, v in numbers.items()}}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--program-seeds", default="")
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--fault-seeds", default="")
    ap.add_argument("--fault", default="half_steps",
                    help="the fault planted for --fault-seeds, one of "
                         "chipbench.faults.FAULTS")
    ap.add_argument("--solves", type=int, default=3)
    args = ap.parse_args(argv)
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # the TPU runtime's logs go under this run's temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.faults import faulty_solver
    from chipbench.harness import load_cell, program_solver
    from repro.launch.compile_cache import use_compile_cache
    import jax
    use_compile_cache()
    cell = load_cell(args.workload)
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": jax.device_count()}
    if device["platform"] != "tpu" or device["count"] < cell.chips:
        print(f"control: needs {cell.chips} TPU chips, found {device}",
              file=sys.stderr)
        return 2
    seeds = lambda s: [int(x) for x in s.split(",") if x]  # noqa: E731
    for seed in seeds(args.program_seeds):
        out = readings(cell, seed, args.solves, program_solver)
        print(json.dumps({"workload": cell.name, "side": "program", **out,
                          "device": device}), flush=True)
    for seed in seeds(args.control_seeds):
        out = readings(cell, seed, 1, reference_solver("bfloat16"))
        print(json.dumps({"workload": cell.name, "side": "control_bfloat16",
                          **out, "device": device}), flush=True)
    for seed in seeds(args.fault_seeds):
        factory, planted = faulty_solver(args.fault, cell.config["engine"])
        with planted:
            out = readings(cell, seed, 1, factory)
        print(json.dumps({"workload": cell.name,
                          "side": f"fault_{args.fault}", **out,
                          "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
