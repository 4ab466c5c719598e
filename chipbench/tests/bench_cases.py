"""Runs of a cell at a size a test can hold, on the CPU, with the harness
driven as in a benchmark run past its look for a chip; optionally with
the timed path broken underneath (a planted fault) or with the plain
reference put in the program's place (the control)."""
from __future__ import annotations

import contextlib
import time

from chipbench.faults import FAULTS, faulty_solver  # noqa: F401

#: per cell: the data, lam, gap target and the outer iterations K the
#: float32 program takes to reach it at test size.  The weak-scaling
#: cell has no check limits of its own yet (they are set from its chip
#: readings when it is added), so its test runs under these
TINY = {
    "part1_4x2.cold": {"data": {"n": 400, "m": 300}, "lam": 0.01,
                       "gap_target": 0.49, "K": 4},
    "synth_realsim_4x2.cold": {"data": {"n": 2000, "m": 600,
                                        "density": 0.02},
                               "lam": 0.1, "gap_target": 0.42, "K": 3},
    "weak_1pct_2x2.cold": {"data": {"n": 800, "m": 400, "density": 0.05},
                           "lam": 1.0, "gap_target": 0.2, "K": 4,
                           "check": {"sample": 3, "unconverged": 0,
                                     "iters_over_K": 0, "map_err": 1e-4,
                                     "gap_report_err": 2e-5}},
}


def tiny_cell(name: str):
    """The cell ``<config>.<traffic>`` from its files, at test size."""
    from chipbench.harness import BENCH, ROOT, Cell, load_json, load_traffic
    config, traffic = name.rsplit(".", 1)
    cfg = load_json(BENCH / "configs" / f"{config}.json")
    tiny = TINY[name]
    cfg["data"] = dict(cfg["data"], **tiny["data"])
    cfg["lam"] = tiny["lam"]
    cfg["gap_target"] = tiny["gap_target"]
    cfg["gap_rule"] = dict(cfg["gap_rule"], K=tiny["K"])
    cfg["check"] = tiny.get("check", cfg.get("check"))
    cfg["outer_iters"] = 12
    chips = cfg["grid"][0] * cfg["grid"][1] if cfg["engine"] != "simulated" \
        else 1
    mix, code = load_traffic(traffic)
    return Cell(name=name, chips=chips, config=cfg, traffic=mix,
                traffic_code=code,
                end_to_end=load_json(ROOT / "BENCHMARK.json")["end_to_end"],
                per_layer=[])


def run(name: str, *, fault: str = None, control: str = None,
        seed: int = 2 ** 31 + 17, seconds: float = 0.5) -> dict:
    """One run of the cell at test size; returns the harness's output
    with ``correct``."""
    import jax
    from chipbench.control import reference_solver
    from chipbench.harness import (is_correct, load_json, program_solver,
                                   run_cell, BENCH)
    cell = tiny_cell(name)
    factory, ctx = program_solver, contextlib.nullcontext()
    if control is not None:
        factory = reference_solver(control)
    elif fault is not None:
        factory, ctx = faulty_solver(fault, cell.config["engine"])
    peaks = load_json(BENCH / "peaks.json")["devices"]["TPU v5 lite"]
    with ctx:
        out = run_cell(cell, seed=seed, seconds=seconds, trace=False,
                       devices=jax.devices()[:cell.chips], peaks=peaks,
                       t_start=time.perf_counter(), solver_factory=factory,
                       log=lambda **kw: None)
    out["correct"] = is_correct(out["numbers"])
    return out
