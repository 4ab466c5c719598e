#!/usr/bin/env python3
"""Chip benchmark of the doubly distributed solvers: one run of one cell.

    python3 chipbench/run.py --workload synth_realsim_4x2.cold --seed 7 \\
        --seconds 20 --trace 0

Run from the root of a checkout.  The cell, its configuration file, its
traffic mix and its metrics are found by name from ``BENCHMARK.json``
(see ``chipbench/harness.py``).  In order: the compile cache goes to
``<checkout>/.jax_cache``; a run without a TPU, or with fewer chips than
the cell asks for, exits 2 and prints no result; the data is made from
``--seed``; one whole solve warms every program up (set-up); solves then
run back to back for ``--seconds``; the answers are checked against the
plain reference.  With ``--trace 1`` the window runs under the profiler
and the per-layer metrics are read from its trace; with ``--trace 0``
the end-to-end metrics are taken by the host clock.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``check``, each number compared with its limit.
The same numbers are the last lines of standard error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str) -> int:
    print(f"chipbench: {msg}", file=sys.stderr)
    return 2


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the benchmark's own compile cache, at a fixed path in the checkout;
    # set before JAX is imported, and taken by the program as given
    cache = ROOT / ".jax_cache"
    cache.mkdir(exist_ok=True)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(cache)
    # the TPU runtime's logs go under this run's temporary directory
    os.environ.setdefault("TPU_LOG_DIR",
                          os.path.join(tempfile.gettempdir(), "tpu_logs"))
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    from chipbench.harness import is_correct, load_cell, peaks_for, run_cell

    cell = load_cell(args.workload)
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        return fail(f"the program under test is missing: {e}")
    import jax
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        return fail(f"no TPU (platform {device['platform']!r}); no result")
    if len(devices) < cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips, found "
                    f"{len(devices)}; no result")
    try:
        peaks = peaks_for(device["kind"])
    except KeyError as e:
        return fail(f"{e.args[0]}; no result")

    out = run_cell(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devices=devices[:cell.chips],
                   peaks=peaks, t_start=t_start)
    numbers = out["numbers"]
    records = out["records"]
    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    result = {"correct": is_correct(numbers), "attempted": len(records),
              "failed": sum(not r.converged for r in records),
              "metrics": out["metrics"], "device": device}
    if args.trace:
        extra = out["extra"]
        device["busy_s"] = extra["busy_s"]
        device["window_s"] = extra["window_s"]
        result["breakdown"] = extra["breakdown"]
    result["check"] = numbers
    for name, x in numbers.items():
        print(f"check {name} {x['value']!r} limit {x['limit']!r}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
