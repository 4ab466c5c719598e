"""Faults planted in the program's timed path, to show that the check
which decides ``correct`` catches each.  The benchmark's own runs never
plant one: ``chipbench/control.py --fault`` reads them at a cell's own
size on the chip, and ``chipbench/tests/`` at test size on the CPU.

Each fault is a ``solver_factory`` for :func:`chipbench.harness.run_cell`:

``state_unchanged``    the outer step returns its state as it was;
``half_batch``         the program solves the first half of the rows
                       alone, its means taken over that half;
``exchange_left_out``  the reductions between cells left out (on one
                       chip, between the vmapped grid's cells);
``answer_altered``     ``w`` altered by 0.1% where it is produced;
``half_steps``         the local kernel takes half an epoch's coordinate
                       steps per outer iteration (the solver's own
                       ``local_steps``).
"""
from __future__ import annotations

import contextlib
import dataclasses

import numpy as np

FAULTS = ("state_unchanged", "half_batch", "answer_altered",
          "exchange_left_out", "half_steps")


@contextlib.contextmanager
def planted_program(fault: str, engine: str):
    """Break the program's timed path underneath ``Solver.solve``."""
    from repro.core import solver as solver_mod
    original = solver_mod.Solver.program

    def program(self, *args, **kwargs):
        prog = original(self, *args, **kwargs)
        if fault == "state_unchanged":
            return dataclasses.replace(prog, step=lambda t, s: s)
        if fault == "answer_altered":
            w_of = prog.w_of
            return dataclasses.replace(
                prog, w_of=lambda s: w_of(s) * (1.0 + 1e-3))
        if fault == "exchange_left_out":
            local = prog.local_step
            if engine == "simulated":
                step = local
            else:
                def step(t, s):
                    return (local(t, s), s[1])
            return dataclasses.replace(prog, step=step)
        raise ValueError(fault)

    solver_mod.Solver.program = program
    try:
        yield
    finally:
        solver_mod.Solver.program = original


def half_batch_solver(cell, problem, seed):
    """The program solving the first half of the rows alone: its means
    run over that half, and the other half's duals come back as zero."""
    from chipbench.harness import program_solver
    from chipbench.problem import Problem
    h = problem.n // 2
    if problem.dense is not None:
        half = Problem(h, problem.m, problem.lam, problem.y[:h],
                       dense=problem.dense[:h])
    else:
        end = problem.indptr[h]
        half = Problem(h, problem.m, problem.lam, problem.y[:h],
                       indptr=problem.indptr[:h + 1],
                       indices=problem.indices[:end],
                       data=problem.data[:end])
    solve = program_solver(cell, half, seed)

    def wrapped(request, start=None):
        iters, converged, gap, w, alpha = solve(request, start)
        alpha = np.concatenate([np.asarray(alpha),
                                np.zeros(problem.n - h, np.float32)])
        return iters, converged, gap, w, alpha

    return wrapped


def half_steps_solver(cell, problem, seed):
    """The program with its local kernel cut to half an epoch: each cell
    takes n_p // 2 coordinate steps per outer iteration."""
    from chipbench.harness import program_solver
    cfg = dict(cell.config)
    n_p = -(-problem.n // cfg["grid"][0])
    cfg["solver_config"] = dict(cfg.get("solver_config", {}),
                                local_steps=n_p // 2)
    return program_solver(dataclasses.replace(cell, config=cfg), problem,
                          seed)


def faulty_solver(fault: str, engine: str):
    """``(solver_factory, context)``: the factory to run and the context
    to run it in, for ``fault``."""
    from chipbench.harness import program_solver
    if fault == "half_batch":
        return half_batch_solver, contextlib.nullcontext()
    if fault == "half_steps":
        return half_steps_solver, contextlib.nullcontext()
    return program_solver, planted_program(fault, engine)
