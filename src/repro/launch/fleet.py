"""Multi-tenant fleet CLI over :mod:`repro.fleet`.

Solve many independent tenant problems in one batched program:

  PYTHONPATH=src python -m repro.launch.fleet \\
      --solver d3ca --tenants 8 --n 256 --m 64 --mesh 2x2 --iters 6

  # mixed shapes: every other tenant gets 50% more rows, so the
  # scheduler packs two shape buckets and drives one batched solve per
  # bucket (retracing is bounded by the bucket count, not by T)
  PYTHONPATH=src python -m repro.launch.fleet \\
      --tenants 8 --shape-mix --metrics

  # the shard_map mesh path (one block per device, all tenants share
  # each collective round); fake the device grid on CPU:
  PYTHONPATH=src python -m repro.launch.fleet \\
      --engine shard_map --mesh 4x2 --force-host-devices 8

  # several rounds over the same tenants: round r warm-starts every
  # tenant from its round r-1 result (the scheduler's warm registry),
  # and --publish-snapshots pushes each tenant's iterates into its own
  # online SnapshotBook + LinearScorer after every round
  PYTHONPATH=src python -m repro.launch.fleet \\
      --tenants 4 --rounds 3 --publish-snapshots

Prints one line per tenant per round and a final JSON summary.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _parse_mesh(s: str):
    try:
        p, q = s.lower().split("x")
        return int(p), int(q)
    except ValueError:
        raise argparse.ArgumentTypeError(f"--mesh expects PxQ, got {s!r}")


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.fleet",
        description="Multi-tenant batched solves (one compiled step for "
                    "T tenants)")
    ap.add_argument("--solver", default="d3ca",
                    help="d3ca | radisa | sfk | admm")
    ap.add_argument("--engine", default="simulated",
                    choices=["simulated", "shard_map", "sync"],
                    help="simulated = vmap grid on one device; shard_map "
                         "(alias: sync) = one block per device.  The "
                         "async/overlap engines are rejected by the fleet "
                         "path (per-build ring state has no tenant axis)")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"],
                    help="cell-local solver backend")
    ap.add_argument("--block-format", default="dense",
                    choices=["dense", "sparse"])
    ap.add_argument("--mesh", type=_parse_mesh, default=(2, 2),
                    metavar="PxQ", help="grid shape, e.g. 2x2")
    ap.add_argument("--tenants", type=int, default=8, metavar="T")
    ap.add_argument("--n", type=int, default=256)
    ap.add_argument("--m", type=int, default=64)
    ap.add_argument("--density", type=float, default=0.05,
                    help="nonzero fraction for --block-format sparse data")
    ap.add_argument("--loss", default="hinge",
                    choices=["hinge", "squared", "logistic"])
    ap.add_argument("--lam", type=float, default=1.0,
                    help="base regularization; tenant i uses "
                         "lam * 0.5^(i mod 3)")
    ap.add_argument("--iters", type=int, default=6)
    ap.add_argument("--tol", type=float, default=None,
                    help="per-tenant early stopping (converged tenants "
                         "freeze exactly; the batch stops when all froze)")
    ap.add_argument("--check-every", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rounds", type=int, default=1,
                    help="resubmit every tenant this many times; round "
                         "r warm-starts from round r-1 (warm registry)")
    ap.add_argument("--max-tenants", type=int, default=None,
                    help="cap tenants per batched solve (bigger buckets "
                         "split into chunks)")
    ap.add_argument("--shape-mix", action="store_true",
                    help="give every other tenant 50%% more rows, "
                         "exercising the scheduler's shape buckets")
    ap.add_argument("--publish-snapshots", action="store_true",
                    help="publish every tenant result into a per-tenant "
                         "online SnapshotBook and refresh its "
                         "LinearScorer (the serving hand-off)")
    ap.add_argument("--force-host-devices", type=int, default=None,
                    help="fake N CPU devices (required before jax init "
                         "for --engine shard_map on a laptop)")
    ap.add_argument("--json-out", default=None,
                    help="write the summary JSON here as well")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="trace the run (fleet/pack, fleet/step, "
                         "fleet/unpack spans) and write Chrome-trace "
                         "JSON here")
    ap.add_argument("--metrics", action="store_true",
                    help="record fleet gauges (tenants per bucket, "
                         "active tenants, per-tenant rel_opt) and print "
                         "the registry snapshot in the summary JSON")
    ap.add_argument("--min-tenants", type=int, default=2,
                    help="--health: WARN when a shape bucket runs with "
                         "fewer tenants than this (starved bucket)")
    from .obs import add_obs_flags
    add_obs_flags(ap)
    return ap


def _report_round(r, problems, results, tenants, books, args):
    """Record + print one round's per-tenant lines."""
    for p in problems:
        res = results[p.tenant_id]
        entry = {
            "tenant": p.tenant_id, "lam": p.lam,
            "n": p.n, "m": p.m, "iters": res.iters,
            "converged": res.converged,
            "objective": (res.history[-1]["objective"]
                          if res.history else None),
        }
        if args.publish_snapshots and p.tenant_id in books:
            entry["snapshot_version"] = \
                books[p.tenant_id].current().version
        tenants[p.tenant_id] = entry
        obj = (f"f={entry['objective']:.6f}"
               if entry["objective"] is not None else "f=?")
        print(f"  round={r} {p.tenant_id:>10} lam={p.lam:<8g} "
              f"n={p.n} iters={res.iters} {obj}"
              + (" converged" if res.converged else ""))


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.force_host_devices:
        if "jax" in sys.modules:
            print("warning: jax already initialized; "
                  "--force-host-devices has no effect", file=sys.stderr)
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count="
              f"{args.force_host_devices}").strip()

    from .compile_cache import use_compile_cache
    use_compile_cache()

    # jax (and everything that imports it) only after the device forcing
    from repro.core import get_solver
    from repro.data import make_sparse_svm_data, make_svm_data
    from repro.fleet import FleetProblem, FleetScheduler

    P, Q = args.mesh
    sparse_fmt = args.block_format == "sparse"

    problems = []
    for i in range(args.tenants):
        n = args.n + (args.n // 2 if args.shape_mix and i % 2 else 0)
        seed = args.seed + i
        if sparse_fmt:
            X, y = make_sparse_svm_data(n, args.m, density=args.density,
                                        seed=seed)
        else:
            X, y = make_svm_data(n, args.m, seed=seed)
        problems.append(FleetProblem(
            tenant_id=f"tenant{i}", loss_name=args.loss, X=X, y=y,
            lam=args.lam * 0.5 ** (i % 3), seed=seed))

    cls = get_solver(args.solver)
    cfg_kw = {"lam": args.lam, "outer_iters": args.iters}
    if args.solver == "admm":
        cfg_kw["rho"] = args.lam
    cfg = cls.config_cls(**cfg_kw)

    tracer = registry = None
    if args.trace:
        from repro.obs import Tracer
        tracer = Tracer()
    if args.metrics:
        from repro.obs import Registry
        registry = Registry()
    from .obs import build_plane
    plane_rules = None
    if args.health:
        from repro.obs import fleet_rules
        plane_rules = fleet_rules(min_tenants=args.min_tenants)
    plane = build_plane(args, rules=plane_rules, registry=registry,
                        meta={"cli": "fleet", "solver": args.solver,
                              "engine": args.engine,
                              "tenants": args.tenants})
    registry = plane.registry if plane.active else registry

    books, scorers = {}, {}

    def on_result(tid, res):
        if not args.publish_snapshots:
            return
        import numpy as np
        if tid not in books:
            from repro.online import SnapshotBook
            from repro.serve import LinearScorer
            books[tid] = SnapshotBook(np.zeros_like(np.asarray(res.w)))
            scorers[tid] = LinearScorer(res.w, loss=args.loss)
        snap = books[tid].publish(res.w, res.alpha, trained_seq=res.iters)
        scorers[tid].update_weights(res.w, snap.version)

    sched = FleetScheduler(
        P=P, Q=Q, solver=args.solver, engine=args.engine,
        local_backend=args.backend, block_format=args.block_format,
        cfg=cfg, tol=args.tol, check_every=args.check_every,
        max_tenants=args.max_tenants, on_result=on_result,
        tracer=plane.tracer_or(tracer), registry=registry,
        monitor=plane.monitor)

    print(f"[fleet] {args.solver} engine={args.engine} "
          f"backend={args.backend} block_format={args.block_format} "
          f"grid={P}x{Q} tenants={args.tenants} loss={args.loss} "
          f"rounds={args.rounds}")

    tenants = {}
    t0 = time.perf_counter()
    with plane.crash_guard():
        for r in range(args.rounds):
            for p in problems:
                sched.submit(p)
            buckets = len(sched.buckets())
            results = sched.run()
            _report_round(r, problems, results, tenants, books, args)
    total_s = time.perf_counter() - t0

    solves = args.tenants * args.rounds
    summary = {
        "solver": args.solver, "engine": args.engine,
        "local_backend": args.backend, "block_format": args.block_format,
        "P": P, "Q": Q, "loss": args.loss, "tenants": args.tenants,
        "rounds": args.rounds, "buckets": buckets,
        "total_s": total_s, "solves_per_s": solves / total_s,
        "results": list(tenants.values()),
    }
    if registry is not None:
        summary["metrics"] = registry.snapshot()
    if plane.active:
        summary["obs"] = plane.finalize()
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        base, _ = os.path.splitext(args.trace)
        tracer.write_jsonl(base + ".jsonl")
        print(f"[fleet] trace: {len(tracer.events)} events -> "
              f"{args.trace} (+ {base + '.jsonl'})")
    print(json.dumps(summary, indent=1))
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(summary, fh, indent=1)
    return summary


if __name__ == "__main__":
    main()
