"""Benchmark harness entry point: one benchmark per paper table/figure,
plus the core solver benchmark, kernel micro-benchmarks and (if dry-run
artifacts exist) the roofline table.  Prints ``name,us_per_call,derived``
CSV rows.

    PYTHONPATH=src python -m benchmarks.run [--quick]
    PYTHONPATH=src python -m benchmarks.run --engine shard_map --backend pallas

The --engine / --backend pair is threaded through every fig benchmark via
the unified solver API.  Everything runs in this one process: a device
belongs to one process at a time, so a child started after this process
touched jax could not reach the chip.  On a CPU run the host platform is
given 32 devices before jax initializes -- ``core`` and ``compress`` lay
their mesh engines over 8, a fig benchmark's ``--engine shard_map`` grid
over up to 32.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))), "src"))

from .common import force_host_devices  # noqa: E402

force_host_devices(32)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="smaller instances (CI-sized)")
    ap.add_argument("--only", default=None,
                    help="comma list: fig3,fig4,fig5,fig6,core,compress,"
                         "kernels,roofline")
    ap.add_argument("--engine", default="simulated",
                    choices=["simulated", "shard_map"])
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    args = ap.parse_args(argv)

    only = set(args.only.split(",")) if args.only else None

    def want(name):
        return only is None or name in only

    eb = ["--engine", args.engine, "--backend", args.backend]
    print("name,us_per_call,derived")

    if want("fig3"):
        from . import fig3_time
        fig3_time.main(["--scale", "0.05" if args.quick else "0.08",
                        "--iters", "8" if args.quick else "15"] + eb)
    if want("fig4"):
        from . import fig4_iters
        fig4_iters.main(["--scale", "0.05" if args.quick else "0.08",
                         "--iters", "20" if args.quick else "50"] + eb)
    if want("fig5"):
        from . import fig5_strong
        fig5_strong.main(["--scale", "0.02" if args.quick else "0.05",
                          "--iters", "10" if args.quick else "25"] + eb)
    if want("fig6"):
        from . import fig6_weak
        fig6_weak.main(["--scale", "0.005" if args.quick else "0.01",
                        "--iters", "6" if args.quick else "12",
                        "--max-p", "3" if args.quick else "4"] + eb)
    if want("core"):
        from . import core_bench
        core_bench.main(["--quick"] if args.quick else [])
    if want("compress"):
        from . import fig_compress
        fig_compress.main(["--quick"] if args.quick else [])
    if want("kernels"):
        from . import kernels_bench
        kernels_bench.main([])
    if want("roofline"):
        from . import roofline
        try:
            roofline.main([])
        except Exception as e:
            print(f"roofline,0.0,unavailable({e!r})")


if __name__ == "__main__":
    main()
