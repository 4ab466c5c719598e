"""Codec x solver sweep for the compressed-communication subsystem.

Runs every solver under ``engine="shard_map"`` across a codec grid
(default: none, identity, int8, fp8, topk:0.25) on the same instance
the core benchmark uses, and lands the rows in ``BENCH_core.json``:

  * one cell per (solver, codec):
    ``{solver}/compress/{backend}/{codec}`` with s_per_iter, final
    rel_opt, and the exact per-step bytes-on-wire (total + per
    collective) -- so the CI regression gate and the trajectory plots
    see compressed runs the same way they see every other cell;
  * a ``compress_sweep`` block with the full suboptimality-vs-epoch
    curves per codec AND the bytes-vs-epoch axis (cumulative
    ``comm_bytes`` from the Solver history) -- the figure's payload:
    rel_opt against *bytes moved*, which is the paper's real cost axis.

Two contracts are asserted, mirroring fig_async's tau-0 check:

  * the identity codec reproduces the uncompressed run exactly
    (max-abs iterate diff == 0) and reports exactly the uncompressed
    payload bytes;
  * int8 cuts the reported reduction bytes >= 3x vs float32.

    PYTHONPATH=src python -m benchmarks.fig_compress [--quick] \\
        [--codecs none,identity,int8,fp8,topk:0.25] \\
        [--solvers d3ca,radisa,admm]

Forces a fake 8-device host platform before jax init (the sweep runs
the mesh engine).  The payload carries the standard provenance stamp
(git_sha / date / quick).
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

import numpy as np  # noqa: E402

from repro.core import (ADMMConfig, D3CAConfig, RADiSAConfig,  # noqa: E402
                        get_solver, objective, serial_sdca)
from repro.data import make_svm_data  # noqa: E402

try:
    from .common import (annotate_wire_predictions, emit_csv_row,
                         phase_fields, provenance, timed)
except ImportError:                    # `python benchmarks/fig_compress.py`
    from common import (annotate_wire_predictions, emit_csv_row,
                        phase_fields, provenance, timed)


def codec_label(spec: str) -> str:
    """Cell-key-friendly codec name ('topk:0.25' -> 'topk0.25')."""
    return spec.replace(":", "")


def sweep_solver(name, cfg, X, y, P, Q, codecs, backend, f_star, reps):
    """One solver across the codec grid.  Returns (cells, curves,
    samples) -- samples feed the wire-time model fit."""
    plain = get_solver(name)(engine="shard_map", local_backend=backend)
    w_plain = plain.solve("hinge", X, y, P=P, Q=Q, cfg=cfg,
                          record_history=False).w
    cells, curves, samples = {}, {}, []
    for codec in codecs:
        compression = None if codec == "none" else codec
        solver = get_solver(name)(engine="shard_map", local_backend=backend,
                                  compression=compression)
        prog = solver.program("hinge", X, y, P=P, Q=Q, cfg=cfg)
        state = prog.step(1, prog.state)          # compile + warm
        t = timed(lambda: prog.step(2, state), reps=reps, warmup=0)
        from repro.obs import Registry
        res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=cfg, f_star=f_star,
                           registry=Registry())
        acct = res.comm_bytes
        entry = {"s_per_iter": t,
                 "rel_opt": res.history[-1]["rel_opt"],
                 "iters": res.iters,
                 "codec": codec,
                 "comm_bytes_per_step": acct["bytes_per_step"],
                 "uncompressed_bytes_per_step":
                     acct["uncompressed_bytes_per_step"],
                 "comm_bytes_by_collective": {
                     cname: c["bytes_per_step"]
                     for cname, c in acct["collectives"].items()}}
        entry.update(phase_fields(res.history))
        if "duality_gap" in res.history[-1]:
            entry["duality_gap"] = res.history[-1]["duality_gap"]
        if codec in ("none", "identity"):
            # contract: identity (and of course none) IS the
            # uncompressed engine, bit for bit -- and reports exactly
            # the uncompressed payload bytes
            diff = float(np.abs(np.asarray(res.w)
                                - np.asarray(w_plain)).max())
            entry["max_abs_diff_vs_uncompressed"] = diff
            assert diff == 0.0, (
                f"{name}: compression={codec!r} diverged from the "
                f"uncompressed engine by {diff:.3e} (expected 0.0)")
            assert (acct["bytes_per_step"]
                    == acct["uncompressed_bytes_per_step"]), (
                f"{name}: {codec} accounting reports "
                f"{acct['bytes_per_step']} B/step, expected the exact "
                f"uncompressed {acct['uncompressed_bytes_per_step']}")
        label = codec_label(codec)
        key = f"{name}/compress/{backend}/{label}"
        if "comm_s" in entry:
            samples.append((acct, {"data": P, "model": Q},
                            entry["comm_s"], key, None))
        cells[key] = entry
        curves[label] = {
            "rel_opt": [h["rel_opt"] for h in res.history],
            "comm_bytes": [h["comm_bytes"] for h in res.history]}
        emit_csv_row(f"fig_compress/{name}/{label}", t * 1e6,
                     f"rel_opt={entry['rel_opt']:.4f},"
                     f"bytes={entry['comm_bytes_per_step']}")
    return cells, curves, samples


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="CI-sized instances")
    ap.add_argument("--codecs", default="none,identity,int8,fp8,topk:0.25",
                    help="comma-separated codec grid ('none' = "
                         "compression disabled entirely)")
    ap.add_argument("--solvers", default="d3ca,radisa,admm")
    ap.add_argument("--backend", default="ref", choices=["ref", "pallas"])
    ap.add_argument("--out", default=os.path.join(ROOT, "BENCH_core.json"))
    ap.add_argument("--reps", type=int, default=3)
    args = ap.parse_args(argv)

    codecs = [c.strip() for c in args.codecs.split(",") if c.strip()]

    P, Q = 4, 2
    n, m = (256, 96) if args.quick else (768, 256)
    inner = 32 if args.quick else 96
    iters = 6 if args.quick else 12
    lam = 1e-1
    X, y = make_svm_data(n, m, seed=0)
    w_ref, _ = serial_sdca("hinge", X, y, lam=lam, epochs=100)
    f_star = float(objective("hinge", X, y, w_ref, lam))

    configs = {
        "d3ca": D3CAConfig(lam=lam, outer_iters=iters, local_steps=inner),
        "radisa": RADiSAConfig(lam=lam, gamma=0.05, outer_iters=iters,
                               L=inner),
        "admm": ADMMConfig(lam=lam, rho=lam, outer_iters=iters),
    }

    # land the rows in BENCH_core.json next to the core grid (fresh
    # payload when core_bench has not run in this checkout)
    if os.path.exists(args.out):
        with open(args.out) as fh:
            payload = json.load(fh)
    else:
        payload = {"cells": {}, "ratios": {}}
    payload.setdefault("cells", {})
    payload["compress_sweep"] = {"codecs": codecs, "n": n, "m": m,
                                 "P": P, "Q": Q, "lam": lam, "iters": iters,
                                 "backend": args.backend, "curves": {}}
    payload["provenance"] = provenance(args.quick)

    all_samples = []
    for name in args.solvers.split(","):
        cells, curves, samples = sweep_solver(name, configs[name], X, y,
                                              P, Q, codecs, args.backend,
                                              f_star, args.reps)
        payload["cells"].update(cells)
        payload["compress_sweep"]["curves"][name] = curves
        all_samples.extend(samples)
        # headline contract: int8 cuts the reported reduction bytes
        # >= 3x vs float32 (int8 payload + one f32 scale per collective)
        none_cell = cells.get(f"{name}/compress/{args.backend}/none")
        int8_cell = cells.get(f"{name}/compress/{args.backend}/int8")
        if none_cell and int8_cell:
            ratio = (none_cell["comm_bytes_per_step"]
                     / int8_cell["comm_bytes_per_step"])
            payload.setdefault("ratios", {})[
                f"{name}/compress/int8_bytes_cut"] = ratio
            assert ratio >= 3.0, (
                f"{name}: int8 cut reduction bytes only {ratio:.2f}x "
                "(expected >= 3x vs float32)")

    if all_samples:
        payload["compress_sweep"]["wire_model"] = annotate_wire_predictions(
            payload["cells"], all_samples)

    with open(args.out, "w") as fh:
        json.dump(payload, fh, indent=1)
    print(f"[fig_compress] wrote {args.out} "
          f"({len(codecs)} codecs x {len(args.solvers.split(','))} solvers)")
    return payload


if __name__ == "__main__":
    main()
