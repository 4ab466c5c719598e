"""Shared utilities of the iteration-count benchmark (``fig4_iters.py``).

This module imports jax lazily: the script calls ``ensure_host_devices``
BEFORE the first jax import so that the mesh engines can fake a P x Q
device grid on CPU.
"""
from __future__ import annotations

import json
import os
import sys

OUT_DIR = os.environ.get(
    "REPRO_BENCH_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 "experiments", "bench"))


def force_host_devices(count: int):
    """Give the CPU backend ``count`` devices, so the mesh engines can lay
    a P x Q grid out on one host.  Only on a CPU run
    (``JAX_PLATFORMS=cpu``): on an accelerator the grid takes the real
    devices.  Must run before jax initializes (the device count is
    locked at first init)."""
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return      # already forced
    if "jax" in sys.modules:
        print("warning: jax already initialized; the mesh engines need "
              "XLA_FLAGS=--xla_force_host_platform_device_count=N set "
              "before the first jax import", file=sys.stderr)
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={count}").strip()


def ensure_host_devices(argv, count: int = 32):
    """:func:`force_host_devices` when the argv selects a mesh engine --
    call it between the stdlib imports and the ``repro.*`` imports of a
    benchmark script."""
    if any("shard_map" in a or "async" in a or "overlap" in a
           for a in argv):
        force_host_devices(count)  # also matches --engine=shard_map forms


def add_engine_args(ap):
    """--engine / --backend / --staleness / --compression knobs."""
    ap.add_argument("--engine", default="simulated",
                    choices=["simulated", "shard_map", "sync", "async",
                             "overlap"])
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"],
                    help="cell-local solver backend")
    ap.add_argument("--staleness", type=int, default=0, metavar="TAU",
                    help="async/overlap engines: reduction delay tau "
                         "(0 = synchronous)")
    ap.add_argument("--compression", default=None, metavar="SPEC",
                    help="codec spec for the declared collectives "
                         "('int8', 'fp8', 'topk:0.1', per-collective "
                         "'dw=int8,z=identity', or an "
                         "'adaptive[:...]' schedule); default: none")
    return ap


def save_result(name: str, payload: dict):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as fh:
        json.dump(payload, fh, indent=1)


def emit_csv_row(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")
