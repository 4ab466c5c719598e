"""Worked observability example: trace a D3CA solve, time its steps and
its host bookkeeping, and export a Chrome-trace you can open in
https://ui.perfetto.dev.

    PYTHONPATH=src python examples/trace_solve.py [--out trace.json]

What it shows:

  * ``Tracer`` spans around the whole solve (``repro.prep`` and its
    cut / send / bind, every outer iteration's step and observation
    with its primal and dual evaluations) -- the same spans, with their
    counters, that a ``jax.profiler`` trace of the solve shows;
  * a ``Registry`` collecting the same run as counters / gauges /
    histograms;
  * the per-iteration ``step_s`` / ``host_s`` fields that telemetry adds
    to ``SolveResult.history``.

Where a step's device time goes (the local kernel, the collectives) is
read from a device profile of the solve (``jax.profiler``): the
``repro.*`` spans and the ``repro.comm.<name>`` scopes mark it there.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="trace.json",
                    help="Chrome-trace JSON path (a .jsonl raw-event "
                         "log is written next to it)")
    ap.add_argument("--iters", type=int, default=10)
    args = ap.parse_args()

    from repro.core import D3CAConfig, get_solver
    from repro.data import make_svm_data
    from repro.obs import Registry, Tracer

    X, y = make_svm_data(800, 200, seed=0)
    cfg = D3CAConfig(lam=1e-1, outer_iters=args.iters, local_steps=64)
    solver = get_solver("d3ca")(engine="simulated")

    tracer, reg = Tracer(), Registry()
    res = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg,
                       tracer=tracer, registry=reg)

    # 1. the timing fields telemetry added to the solve history
    print("per-iteration step and host time:")
    for h in res.history:
        print(f"  t={h['iter']:3d}  step {h['step_s'] * 1e3:7.3f} ms"
              f"  (obs host {h['host_s'] * 1e3:7.3f} ms)"
              f"   f={h['objective']:.6f}")

    # 2. span totals straight off the tracer
    solve_s = tracer.total("repro.solve")
    print(f"\nspan totals over {solve_s * 1e3:.1f} ms of solve:")
    for name in ("repro.prep", "repro.prep.partition", "repro.prep.transfer",
                 "repro.prep.bind", "repro.iter",
                 "repro.step", "repro.observe", "repro.observe.primal",
                 "repro.observe.dual", "repro.result"):
        t = tracer.total(name)
        print(f"  {name:<22s} {t * 1e3:8.2f} ms  "
              f"({100 * t / solve_s:5.1f}%)")

    # 3. the registry snapshot
    snap = reg.snapshot()
    print("\nregistry snapshot (counters + a few gauges):")
    print(json.dumps({"counters": snap["counters"],
                      "gauges": snap["gauges"]}, indent=1))

    # 4. export: drag args.out into ui.perfetto.dev (or chrome://tracing)
    tracer.write_chrome_trace(args.out)
    base, _ = os.path.splitext(args.out)
    tracer.write_jsonl(base + ".jsonl")
    print(f"\nwrote {args.out} (+ {base}.jsonl) -- "
          f"{len(tracer.events)} events; open in https://ui.perfetto.dev")


if __name__ == "__main__":
    main()
