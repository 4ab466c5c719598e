"""Shared benchmark utilities.

This module imports jax lazily: the benchmarks call
``force_host_devices`` / ``ensure_host_devices`` BEFORE the first jax
import so that the mesh engines can fake a P x Q device grid on CPU.
"""
from __future__ import annotations

import datetime
import json
import os
import subprocess
import sys
import time

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
        return      # already forced (possibly by an earlier fig module)
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
    """--engine / --backend / --block-format / --staleness /
    --compression knobs shared by the fig benchmarks."""
    ap.add_argument("--engine", default="simulated",
                    choices=["simulated", "shard_map", "sync", "async",
                             "overlap"])
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"],
                    help="cell-local solver backend")
    ap.add_argument("--block-format", default="dense",
                    choices=["dense", "sparse"],
                    help="per-cell layout (sparse = padded-ELL cells)")
    ap.add_argument("--staleness", type=int, default=0, metavar="TAU",
                    help="async/overlap engines: reduction delay tau "
                         "(0 = synchronous)")
    ap.add_argument("--compression", default=None, metavar="SPEC",
                    help="codec spec for the declared collectives "
                         "('int8', 'fp8', 'topk:0.1', per-collective "
                         "'dw=int8,z=identity', or an "
                         "'adaptive[:...]' schedule); default: none")
    ap.add_argument("--topology", default=None, metavar="SPEC",
                    help="hierarchical reduction topology, e.g. "
                         "'pods=2:int8' (default: flat)")
    return ap


def provenance(quick: bool) -> dict:
    """Stamp for BENCH_*.json payloads: the regression gate and
    trajectory plots must be able to trust what produced a number."""
    try:
        sha = subprocess.run(
            ["git", "rev-parse", "HEAD"], capture_output=True, text=True,
            cwd=os.path.dirname(os.path.abspath(__file__)),
            timeout=10).stdout.strip() or "unknown"
    except (OSError, subprocess.SubprocessError):
        sha = "unknown"
    return {
        "git_sha": sha,
        "date": datetime.datetime.now(datetime.timezone.utc).isoformat(
            timespec="seconds"),
        "quick": bool(quick),
    }


def phase_fields(history) -> dict:
    """Mean per-iteration phase attribution over a timed solve's history
    (``step_s`` / ``local_s`` / ``comm_s`` / ``host_s`` -- present when
    the solve ran under a tracer or registry).  Empty dict when
    telemetry was off, so callers can ``cell.update(...)`` blindly."""
    timed_hist = [h for h in history if "step_s" in h]
    out = {}
    if timed_hist:
        k = float(len(timed_hist))
        for field in ("step_s", "local_s", "comm_s", "host_s",
                      "comm_exposed_s", "comm_hidden_s"):
            vals = [h[field] for h in timed_hist if field in h]
            if len(vals) == len(timed_hist):
                out[field] = sum(vals) / k
    return out


def annotate_wire_predictions(cells: dict, samples, algo: str = "ring"):
    """Fit the alpha-beta wire-time model on a sweep's own measured
    per-step ``comm_s`` and stamp every sampled cell with predicted
    seconds + relative error (``predicted_comm_s`` /
    ``predicted_rel_err``).

    Each sample is ``(acct, sizes, measured_comm_s, cell_key,
    topology_or_None)`` -- ``acct`` the program's wire accounting,
    ``sizes`` the logical axis extents.  Returns the ``wire_model``
    report block for the sweep payload (fitted alpha/beta + per-cell
    predicted-vs-measured).
    """
    import dataclasses

    from repro.core.comm_model import fit_link, predict_comm_s
    link = fit_link([(acct, sizes, t) for acct, sizes, t, _, _ in samples],
                    algo=algo, name="fitted")
    report = {"alpha_s": link.alpha_s,
              "beta_s_per_byte": link.beta_s_per_byte,
              "bandwidth_gbps": link.bandwidth_gbps, "algo": algo,
              "cells": {}}
    for acct, sizes, measured, key, topo in samples:
        if topo is not None:
            topo = dataclasses.replace(topo, intra=link, inter=link)
        pred = predict_comm_s(acct, sizes, topology=topo, link=link,
                              algo=algo)
        rel_err = (abs(pred["total_s"] - measured) / measured
                   if measured > 0 else None)
        cells[key]["predicted_comm_s"] = pred["total_s"]
        cells[key]["predicted_rel_err"] = rel_err
        report["cells"][key] = {"predicted_s": pred["total_s"],
                                "measured_s": measured,
                                "rel_err": rel_err}
    return report


def save_result(name: str, payload: dict):
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, f"{name}.json"), "w") as fh:
        json.dump(payload, fh, indent=1)


def timed(fn, *args, reps=1, warmup=1):
    import jax
    import numpy as np
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        ts.append(time.perf_counter() - t0)
    return float(np.median(ts))


def emit_csv_row(name: str, us_per_call: float, derived: str = ""):
    print(f"{name},{us_per_call:.1f},{derived}")
