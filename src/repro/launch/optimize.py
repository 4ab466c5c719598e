"""CLI over the unified solver framework (``repro.core.solver``).

Run any of the paper's doubly distributed optimizers on a synthetic
dataset under any (engine, local_backend) pair:

  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver d3ca --dataset dense --n 1600 --m 400 --mesh 4x2 \\
      --engine simulated --backend ref --loss hinge --lam 0.1 --iters 15

  # the production shard_map engine needs one device per grid cell;
  # --force-host-devices N fakes them on CPU (set before jax init):
  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver radisa --mesh 4x2 --engine shard_map --backend pallas \\
      --force-host-devices 8

  # news20-scale sparse instances: --block-format sparse keeps every
  # block in the padded-ELL cell format (memory ~ nnz, never densified)
  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver d3ca --dataset sparse --density 0.01 --n 20000 --m 50000 \\
      --block-format sparse

  # bounded-staleness reductions (Hogwild-style delayed psum): the async
  # engine applies every CommSchedule collective with delay tau;
  # --staleness 0 reproduces --engine shard_map bit for bit
  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver d3ca --mesh 4x2 --engine async --staleness 2 \\
      --force-host-devices 8

  # compressed reductions: quantize every declared collective (or name
  # them individually) with error feedback; the summary reports exact
  # bytes-on-wire per outer step.  --compression identity is
  # bit-identical to no compression
  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver d3ca --mesh 4x2 --engine shard_map \\
      --compression int8 --force-host-devices 8
  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver radisa --compression "dw=topk:0.1,z=identity"

  # communication overlap: dispatch reductions asynchronously and hide
  # them behind tau steps of local solve (--staleness 0 is bit-identical
  # to shard_map); --topology splits the reductions into full-precision
  # intra-pod + codec-compressed cross-pod tiers; adaptive compression
  # switches codec stages as convergence flattens
  PYTHONPATH=src python -m repro.launch.optimize \\
      --solver d3ca --mesh 4x2 --engine overlap --staleness 2 \\
      --topology "pods=2:int8" --compression "adaptive" \\
      --force-host-devices 8

Prints one line per outer iteration (objective, duality gap when the
solver has a dual, relative optimality when --ref-epochs > 0) and a
final JSON summary.
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
        prog="repro.launch.optimize",
        description="Unified doubly distributed solver CLI")
    ap.add_argument("--solver", default="d3ca",
                    help="d3ca | radisa | admm (see get_solver)")
    ap.add_argument("--engine", default="simulated",
                    choices=["simulated", "shard_map", "sync", "async",
                             "overlap"],
                    help="simulated = vmap grid on one device; shard_map "
                         "(alias: sync) = one block per device, synchronous "
                         "reductions; async = same mesh with "
                         "bounded-staleness reductions (--staleness); "
                         "overlap = async dispatch with donated in-flight "
                         "reduction slots so the local solve hides the "
                         "wire")
    ap.add_argument("--staleness", type=int, default=0, metavar="TAU",
                    help="async/overlap engines: apply every declared "
                         "reduction with delay TAU outer iterations "
                         "(0 = synchronous, identical to shard_map)")
    ap.add_argument("--compression", default=None, metavar="SPEC",
                    help="compress the declared collectives: a codec for "
                         "all of them ('int8', 'fp8', 'topk:0.1', "
                         "'identity'), per-collective "
                         "('w_contrib=int8,dalpha=identity'), or an "
                         "adaptive schedule "
                         "('adaptive[:topk:0.25->int8][@slope=..]') that "
                         "switches codec stages as convergence flattens; "
                         "codecs carry error feedback, and the summary "
                         "reports exact bytes-on-wire (default: no "
                         "compression)")
    ap.add_argument("--topology", default=None, metavar="SPEC",
                    help="hierarchical reductions, e.g. 'pods=2:int8': "
                         "full-precision psum within each pod, "
                         "codec-compressed across pods (default: flat)")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"],
                    help="cell-local solver backend")
    ap.add_argument("--block-format", default="dense",
                    choices=["dense", "sparse"],
                    help="per-cell data layout: dense (n_p, m_q) tiles or "
                         "padded-ELL sparse cells (memory ~ nnz)")
    ap.add_argument("--mesh", type=_parse_mesh, default=(4, 2),
                    metavar="PxQ", help="grid shape, e.g. 4x2")
    ap.add_argument("--dataset", default="dense",
                    choices=["dense", "sparse", "libsvm"])
    ap.add_argument("--libsvm-path", default=None,
                    help="path for --dataset libsvm (streamed into CSR "
                         "when --block-format sparse)")
    ap.add_argument("--n", type=int, default=1600)
    ap.add_argument("--m", type=int, default=400)
    ap.add_argument("--problems", type=int, default=1, metavar="N",
                    help="fan out: solve N independent synthetic "
                         "instances (seeds seed..seed+N-1) as ONE "
                         "batched fleet solve sharing every collective "
                         "round and one compiled step (see "
                         "repro.launch.fleet for the multi-tenant "
                         "scheduler; engine simulated/shard_map only)")
    ap.add_argument("--density", type=float, default=0.05,
                    help="nonzero fraction for --dataset sparse")
    ap.add_argument("--loss", default="hinge",
                    choices=["hinge", "squared", "logistic"])
    ap.add_argument("--lam", type=float, default=1e-1)
    ap.add_argument("--iters", type=int, default=15)
    ap.add_argument("--tol", type=float, default=None,
                    help="early-stopping tolerance (see Solver.solve)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ref-epochs", type=int, default=100,
                    help="serial SDCA epochs for f*; 0 skips rel-opt")
    ap.add_argument("--force-host-devices", type=int, default=None,
                    help="fake N CPU devices (required before jax init "
                         "for --engine shard_map on a laptop)")
    ap.add_argument("--json-out", default=None,
                    help="write the summary JSON here as well")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="trace the solve and write Chrome-trace JSON "
                         "here (open in chrome://tracing or "
                         "ui.perfetto.dev); spans cover data prep, every "
                         "outer iteration, its step and its objective "
                         "evaluations.  OUT.jsonl is written next to it "
                         "with the raw events")
    ap.add_argument("--metrics", action="store_true",
                    help="record solver metrics into a registry and "
                         "print its snapshot in the summary JSON")
    from .obs import add_obs_flags
    add_obs_flags(ap)
    return ap


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.staleness < 0:
        ap.error(f"--staleness {args.staleness} is negative; the reduction "
                 "delay tau must be >= 0 (0 = synchronous)")
    if args.staleness > 0 and args.engine not in ("async", "overlap"):
        ap.error(f"--staleness {args.staleness} only works with "
                 f"--engine async or --engine overlap; --engine "
                 f"{args.engine} applies every reduction synchronously "
                 "(pass --engine async/overlap, or drop --staleness)")

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
    from repro.core import get_solver, objective, serial_sdca
    from repro.data import (load_libsvm, load_libsvm_csr,
                            make_sparse_svm_csr, make_sparse_svm_data,
                            make_svm_data)

    P, Q = args.mesh
    sparse_fmt = args.block_format == "sparse"

    if args.problems > 1:
        return _fanout(ap, args, P, Q)

    if args.dataset == "dense":
        X, y = make_svm_data(args.n, args.m, seed=args.seed)
    elif args.dataset == "libsvm":
        if not args.libsvm_path:
            build_parser().error("--dataset libsvm needs --libsvm-path")
        loader = load_libsvm_csr if sparse_fmt else load_libsvm
        X, y = loader(args.libsvm_path)
    elif sparse_fmt:
        # CSR all the way down: the dense matrix is never materialized
        X, y = make_sparse_svm_csr(args.n, args.m, density=args.density,
                                   seed=args.seed)
    else:
        X, y = make_sparse_svm_data(args.n, args.m, density=args.density,
                                    seed=args.seed)

    f_star = None
    if args.ref_epochs > 0:
        n_, m_ = X.shape
        if hasattr(X, "toarray") and n_ * m_ > 20_000_000:
            print(f"[optimize] skipping f* reference: densifying "
                  f"{n_}x{m_} for serial SDCA would need "
                  f"{n_ * m_ * 4 / 1e9:.1f} GB (pass --ref-epochs 0 to "
                  "silence)", file=sys.stderr)
        else:
            X_ref = X.toarray() if hasattr(X, "toarray") else X
            w_ref, _ = serial_sdca(args.loss, X_ref, y, lam=args.lam,
                                   epochs=args.ref_epochs)
            f_star = float(objective(args.loss, X_ref, y, w_ref, args.lam))

    cls = get_solver(args.solver)
    solver = cls(engine=args.engine, local_backend=args.backend,
                 block_format=args.block_format, staleness=args.staleness,
                 compression=args.compression, topology=args.topology)
    cfg_kw = {"lam": args.lam, "outer_iters": args.iters}
    if args.solver == "admm":
        cfg_kw["rho"] = args.lam
    cfg = cls.config_cls(**cfg_kw)

    stale = (f" staleness={args.staleness}"
             if args.engine in ("async", "overlap") else "")
    comp = (f" compression={solver.compression_spec}"
            if solver.compression is not None else "")
    if solver.topology is not None:
        comp += f" topology={solver.topology_spec}"
    print(f"[optimize] {args.solver} engine={args.engine}{stale}{comp} "
          f"backend={args.backend} block_format={args.block_format} "
          f"grid={P}x{Q} "
          f"{args.dataset}({X.shape[0]}x{X.shape[1]}) loss={args.loss} "
          f"lam={args.lam}")
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
        from repro.obs import solver_rules
        plane_rules = solver_rules()
    plane = build_plane(args, rules=plane_rules, registry=registry,
                        meta={"cli": "optimize", "solver": args.solver,
                              "engine": args.engine})
    registry = plane.registry if plane.active else registry
    with plane.crash_guard():
        res = solver.solve(args.loss, X, y, P=P, Q=Q, cfg=cfg,
                           tol=args.tol, f_star=f_star,
                           tracer=plane.tracer_or(tracer),
                           registry=registry, monitor=plane.monitor)
    if res.comm_bytes is not None:
        acct = res.comm_bytes
        detail = ", ".join(
            f"{name}: {c['bytes_per_step']}B/step [{c['codec']}]"
            for name, c in acct["collectives"].items())
        print(f"[optimize] wire: {acct['bytes_per_step']} B/step "
              f"(uncompressed {acct['uncompressed_bytes_per_step']}) -- "
              f"{detail}")
    for h in res.history:
        line = (f"  t={h['iter']:3d}  {h['time_s']:7.2f}s  "
                f"f={h['objective']:.6f}")
        if "duality_gap" in h:
            line += f"  gap={h['duality_gap']:.3e}"
        if "rel_opt" in h:
            line += f"  rel_opt={h['rel_opt']:.4f}"
        print(line)

    summary = {
        "solver": res.solver, "engine": res.engine,
        "staleness": res.staleness,
        "local_backend": res.local_backend,
        "block_format": res.block_format, "P": P, "Q": Q,
        "n": int(X.shape[0]), "m": int(X.shape[1]), "loss": args.loss,
        "lam": args.lam, "iters": res.iters, "converged": res.converged,
        "objective": res.history[-1]["objective"] if res.history else None,
        "rel_opt": res.history[-1].get("rel_opt") if res.history else None,
        "total_s": res.history[-1]["time_s"] if res.history else None,
        "compression": res.compression,
        "topology": res.topology,
        "comm_bytes_per_step": (res.comm_bytes or {}).get("bytes_per_step"),
        "comm_bytes_total": (res.history[-1].get("comm_bytes")
                             if res.history else None),
    }
    if registry is not None:
        summary["metrics"] = registry.snapshot()
    if plane.active:
        summary["obs"] = plane.finalize()
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        base, _ = os.path.splitext(args.trace)
        tracer.write_jsonl(base + ".jsonl")
        print(f"[optimize] trace: {len(tracer.events)} events -> "
              f"{args.trace} (+ {base + '.jsonl'})")
    print(json.dumps(summary, indent=1))
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump({"summary": summary, "history": res.history}, fh,
                      indent=1)
    return summary


def _fanout(ap, args, P, Q):
    """--problems N: one batched fleet solve over N synthetic instances."""
    import time

    from repro.core import get_solver
    from repro.data import (make_sparse_svm_csr, make_sparse_svm_data,
                            make_svm_data)
    from repro.fleet import FleetProblem, FleetSolver

    if args.dataset == "libsvm":
        ap.error("--problems fans out synthetic instances; use --dataset "
                 "dense or sparse (one libsvm file is one problem)")
    sparse_fmt = args.block_format == "sparse"

    probs = []
    for i in range(args.problems):
        seed = args.seed + i
        if args.dataset == "dense":
            X, y = make_svm_data(args.n, args.m, seed=seed)
        elif sparse_fmt:
            X, y = make_sparse_svm_csr(args.n, args.m,
                                       density=args.density, seed=seed)
        else:
            X, y = make_sparse_svm_data(args.n, args.m,
                                        density=args.density, seed=seed)
        probs.append(FleetProblem(tenant_id=f"p{i}", loss_name=args.loss,
                                  X=X, y=y, lam=args.lam, seed=seed))

    try:
        fleet = FleetSolver(solver=args.solver, engine=args.engine,
                            local_backend=args.backend,
                            block_format=args.block_format,
                            staleness=args.staleness,
                            compression=args.compression,
                            topology=args.topology)
    except ValueError as e:
        ap.error(str(e))

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
        plane_rules = fleet_rules()
    plane = build_plane(args, rules=plane_rules, registry=registry,
                        meta={"cli": "optimize", "solver": args.solver,
                              "engine": args.engine,
                              "problems": args.problems})
    registry = plane.registry if plane.active else registry

    print(f"[optimize] {args.solver} engine={fleet.engine} "
          f"backend={args.backend} block_format={args.block_format} "
          f"grid={P}x{Q} problems={args.problems} "
          f"{args.dataset}({args.n}x{args.m}) loss={args.loss} "
          f"lam={args.lam} (fleet fan-out)")
    t0 = time.perf_counter()
    with plane.crash_guard():
        results = fleet.solve_batch(probs, P=P, Q=Q, cfg=cfg, tol=args.tol,
                                    tracer=plane.tracer_or(tracer),
                                    registry=registry)
    total_s = time.perf_counter() - t0
    for p, res in zip(probs, results):
        obj = res.history[-1]["objective"] if res.history else None
        print(f"  {p.tenant_id:>6} seed={p.seed} iters={res.iters} "
              + (f"f={obj:.6f}" if obj is not None else "f=?")
              + (" converged" if res.converged else ""))

    summary = {
        "solver": args.solver, "engine": fleet.engine,
        "local_backend": args.backend,
        "block_format": args.block_format, "P": P, "Q": Q,
        "n": args.n, "m": args.m, "loss": args.loss, "lam": args.lam,
        "problems": args.problems, "total_s": total_s,
        "solves_per_s": args.problems / total_s,
        "results": [{
            "problem": p.tenant_id, "seed": p.seed, "iters": r.iters,
            "converged": r.converged,
            "objective": (r.history[-1]["objective"]
                          if r.history else None),
        } for p, r in zip(probs, results)],
    }
    if registry is not None:
        summary["metrics"] = registry.snapshot()
    if plane.active:
        summary["obs"] = plane.finalize()
    if tracer is not None:
        tracer.write_chrome_trace(args.trace)
        base, _ = os.path.splitext(args.trace)
        tracer.write_jsonl(base + ".jsonl")
        print(f"[optimize] trace: {len(tracer.events)} events -> "
              f"{args.trace} (+ {base + '.jsonl'})")
    print(json.dumps(summary, indent=1))
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(summary, fh, indent=1)
    return summary


if __name__ == "__main__":
    main()
