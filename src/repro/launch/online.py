"""CLI over the online learning service (``repro.online``).

Drives a synthetic observation stream through the full request
lifecycle -- admission queue, grid store, warm-started gated solver
passes, snapshot publish, live scoring -- and prints a per-round
staleness/throughput report plus a final JSON summary:

  PYTHONPATH=src python -m repro.launch.online \\
      --m 64 --capacity 512 --mesh 2x2 --rounds 20 --batch 32

  # production shard_map engine (one device per grid cell):
  PYTHONPATH=src python -m repro.launch.online \\
      --mesh 4x2 --engine shard_map --backend pallas \\
      --force-host-devices 8

  # persist every published version and recover from the newest one:
  PYTHONPATH=src python -m repro.launch.online --ckpt-dir /tmp/online_ck

  # telemetry: Chrome-trace spans of ingest/update/swap/score plus the
  # staleness gauge / update histograms in the summary JSON
  PYTHONPATH=src python -m repro.launch.online --trace /tmp/online.json \\
      --metrics
"""
from __future__ import annotations

import argparse
import json
import os
import sys


def _parse_mesh(s: str):
    try:
        p, q = s.lower().split("x")
        return int(p), int(q)
    except ValueError:
        raise argparse.ArgumentTypeError(f"--mesh expects PxQ, got {s!r}")


def build_parser():
    ap = argparse.ArgumentParser(
        prog="repro.launch.online",
        description="Streaming doubly distributed solver service CLI")
    ap.add_argument("--solver", default="d3ca",
                    help="row-gate-capable solver (d3ca)")
    ap.add_argument("--engine", default="simulated",
                    choices=["simulated", "shard_map", "sync", "async",
                             "overlap"])
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--block-format", default="dense",
                    choices=["dense", "sparse"])
    ap.add_argument("--staleness", type=int, default=0, metavar="TAU")
    ap.add_argument("--compression", default=None, metavar="SPEC")
    ap.add_argument("--topology", default=None, metavar="SPEC")
    ap.add_argument("--mesh", type=_parse_mesh, default=(2, 2),
                    metavar="PxQ", help="grid shape, e.g. 2x2")
    ap.add_argument("--m", type=int, default=64, help="feature dimension")
    ap.add_argument("--capacity", type=int, default=512,
                    help="observation window (GridStore rows)")
    ap.add_argument("--loss", default="hinge",
                    choices=["hinge", "squared", "logistic"])
    ap.add_argument("--lam", type=float, default=1e-2)
    ap.add_argument("--passes", type=int, default=2,
                    help="warm-started outer iterations per drained batch")
    ap.add_argument("--rounds", type=int, default=20,
                    help="stream rounds (each: submit, update, score)")
    ap.add_argument("--batch", type=int, default=32,
                    help="observations per stream round")
    ap.add_argument("--score-batch", type=int, default=128,
                    help="scoring requests per round")
    ap.add_argument("--queue-capacity", type=int, default=4096)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default=None,
                    help="persist published versions here (and recover "
                         "from the newest before streaming)")
    ap.add_argument("--force-host-devices", type=int, default=None,
                    help="fake N CPU devices (before jax init; needed "
                         "for --engine shard_map on a laptop)")
    ap.add_argument("--json-out", default=None)
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="write Chrome-trace JSON of the "
                         "ingest/update/swap/score spans")
    ap.add_argument("--metrics", action="store_true",
                    help="include the service's metrics snapshot "
                         "(staleness gauge, update/swap histograms, "
                         "throughput counters) in the summary JSON")
    ap.add_argument("--max-staleness", type=float, default=60.0,
                    help="--health: CRIT when the served snapshot is "
                         "older than this many seconds")
    ap.add_argument("--max-lag", type=float, default=10_000,
                    help="--health: CRIT when the served model trails "
                         "the stream by more than this many admitted "
                         "observations")
    from .obs import add_obs_flags
    add_obs_flags(ap)
    return ap


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

    import numpy as np

    from repro.core import get_solver, objective
    from repro.launch.mesh import make_grid_mesh
    from repro.online import OnlineConfig, OnlineSolverService

    P, Q = args.mesh
    mesh = None if args.engine == "simulated" else make_grid_mesh(P, Q)
    manager = None
    if args.ckpt_dir:
        from repro.checkpoint.manager import CheckpointManager
        manager = CheckpointManager(args.ckpt_dir, keep_n=3)
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
        from repro.obs import online_rules
        plane_rules = online_rules(max_staleness_s=args.max_staleness,
                                   max_lag=args.max_lag)
    plane = build_plane(args, rules=plane_rules, registry=registry,
                        meta={"cli": "online", "solver": args.solver,
                              "engine": args.engine})
    registry = plane.registry if plane.active else registry

    cls = get_solver(args.solver)
    cfg = cls.config_cls(lam=args.lam)
    config = OnlineConfig(
        m=args.m, capacity=args.capacity, P=P, Q=Q, loss=args.loss,
        solver=args.solver, engine=args.engine,
        local_backend=args.backend, block_format=args.block_format,
        staleness=args.staleness, compression=args.compression,
        topology=args.topology, solver_cfg=cfg, passes=args.passes,
        queue_capacity=args.queue_capacity)
    svc = OnlineSolverService(config, mesh=mesh, manager=manager,
                              tracer=plane.tracer_or(tracer),
                              registry=registry, monitor=plane.monitor)
    recovered = svc.recover()
    if recovered is not None:
        print(f"[online] recovered snapshot version {recovered} from "
              f"{args.ckpt_dir}")

    rng = np.random.default_rng(args.seed)
    w_star = np.linspace(-1.0, 1.0, args.m).astype(np.float32)

    def stream(b):
        X = rng.normal(size=(b, args.m)).astype(np.float32)
        y = np.sign(X @ w_star + 0.1 * rng.normal(size=b))
        y = np.where(y == 0, 1.0, y).astype(np.float32)
        return X, y

    print(f"[online] {args.solver} engine={args.engine} "
          f"backend={args.backend} grid={P}x{Q} m={args.m} "
          f"capacity={svc.store.capacity} passes={args.passes} "
          f"loss={args.loss} lam={args.lam}")
    with plane.crash_guard():
        for r in range(args.rounds):
            svc.submit(*stream(args.batch))
            version = svc.run_pending()
            Xs, ys = stream(args.score_batch)
            acc = float(np.mean(svc.predict(Xs) * ys > 0)) \
                if args.loss != "logistic" else float("nan")
            mask = svc.store.filled_mask > 0
            f = float(objective(args.loss, svc.store.X[mask],
                                svc.store.y[mask],
                                svc.book.current().w, args.lam))
            print(f"  round={r:3d} version={version} "
                  f"filled={svc.store.filled}/{svc.store.capacity} "
                  f"f={f:.5f} acc={acc:.3f} lag={svc.version_lag} "
                  f"staleness={svc.staleness_s * 1e3:.1f}ms")
    if manager is not None:
        svc.book.flush()

    summary = dict(svc.stats())
    summary.update(solver=args.solver, engine=args.engine,
                   backend=args.backend, block_format=args.block_format,
                   P=P, Q=Q, m=args.m, loss=args.loss, lam=args.lam,
                   passes=args.passes, rounds=args.rounds,
                   batch=args.batch, objective=f)
    if registry is not None:
        summary["metrics"] = registry.snapshot()
    if plane.active:
        summary["obs"] = plane.finalize()
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        base, _ = os.path.splitext(args.trace)
        tracer.write_jsonl(base + ".jsonl")
        print(f"[online] trace: {len(tracer.events)} events -> "
              f"{args.trace} (+ {base + '.jsonl'})")
    print(json.dumps(summary, indent=1))
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(summary, fh, indent=1)
    return summary


if __name__ == "__main__":
    main()
