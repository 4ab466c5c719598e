"""One run of one cell: build, warm up, measure, check.

Everything cell-specific comes from files found by name:
``configs/<config>.json`` (the problem, the solver and its knobs, the
gap target and the limits of the check), ``traffic/<traffic>.json`` (the
mix's parameters, read by :func:`requests` and :func:`closed_loop`),
``traffic/<traffic>.py`` where a mix needs code of its own (it may
define ``requests`` and ``window`` in their place), and
``metrics/<metric>.py`` (one reader per per-layer metric).  This module
names no cell, mix or metric.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib
import importlib.util
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional

import numpy as np

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

#: JAX's monitoring events counted in set-up and in the window: backend
#: compiles (none in the window), jaxpr traces (the program traces shapes
#: for every program it binds, a cost each solve pays), and the
#: persistent compile cache's hits and misses (a run after the first in
#: a checkout misses nothing)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration",
                  "/jax/compilation_cache/cache_hits",
                  "/jax/compilation_cache/cache_misses")


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def peaks_for(kind: str) -> dict:
    """The published peaks of one chip of ``kind`` (``device_kind`` as
    JAX reports it); a kind missing from ``peaks.json`` is an error."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in "
                       "chipbench/peaks.json")
    return table[kind]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    #: the BENCHMARK.json entries of the metrics this cell reports
    end_to_end: List[dict]
    per_layer: List[dict]
    #: ``traffic/<traffic>.py`` where the mix has one, else None
    traffic_code: object = None


def load_traffic(name: str, root: Path = BENCH / "traffic"):
    """The mix ``name``: its parameters from ``<root>/<name>.json``, and
    the module ``<root>/<name>.py`` where there is one (else None)."""
    mix = load_json(root / f"{name}.json")
    path = root / f"{name}.py"
    if not path.exists():
        return mix, None
    spec = importlib.util.spec_from_file_location(
        f"chipbench_traffic_{name}", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return mix, module


def load_cell(name: str, bench: Optional[dict] = None) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with its files."""
    bench = bench if bench is not None else load_json(ROOT / "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise SystemExit(f"chipbench: no workload {name!r} in BENCHMARK.json")
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    e2e_names = {m["name"] for m in e2e}
    # a per-layer metric without a workloads list belongs to every cell
    # that reports the end-to-end metric it moves
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", ())
                 or ("workloads" not in m and m["moves"] in e2e_names)]
    mix, code = load_traffic(entry["traffic"])
    return Cell(name=name, chips=int(entry["chips"]),
                config=load_json(BENCH / "configs" / f"{entry['config']}.json"),
                traffic=mix, traffic_code=code,
                end_to_end=e2e, per_layer=per_layer)


class CompileCounter:
    """Counts :data:`COMPILE_EVENTS` while ``armed``."""

    def __init__(self):
        self.armed = False
        self.counts = {e: 0 for e in COMPILE_EVENTS}

    def _listen(self, event, *args, **kwargs):
        if self.armed and event in self.counts:
            self.counts[event] += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        jax.monitoring.register_event_listener(self._listen)
        return self

    def __exit__(self, *exc):
        from jax._src import monitoring
        monitoring.unregister_event_duration_listener(self._listen)
        monitoring.unregister_event_listener(self._listen)
        return False


@dataclasses.dataclass
class SolveRecord:
    index: int
    begin: float            # host clock, s
    end: float
    iters: int
    converged: bool
    #: the duality gap the solve reported last
    gap: float
    w: object
    alpha: object
    #: the per-solve arguments the solve ran with (see :func:`requests`)
    request: dict = dataclasses.field(default_factory=dict)


# ---------------------------------------------------------------------------
# the system under test, and the traffic that drives it
# ---------------------------------------------------------------------------

#: what one solve may be asked, and the configuration key each defaults
#: to: its regularizer, its gap target, the outer iterations it should
#: take to reach it (checked exactly), and ``start``: ``"cold"`` (w = 0,
#: alpha = 0) or ``"warm"`` (from the previous solve's answer)
REQUEST_KEYS = ("lam", "gap_target", "K", "start")


def requests(cell: Cell, seed: int) -> List[dict]:
    """The per-solve arguments the window cycles through, in order.

    The mix's ``schedule`` (default: one entry) lists them; each entry
    may set any of :data:`REQUEST_KEYS`, the rest come from the
    configuration (``K`` from its ``gap_rule``) and ``start`` from the
    mix.  A ``traffic/<traffic>.py`` that defines ``requests(cell,
    seed)`` gives them instead."""
    code = cell.traffic_code
    if code is not None and hasattr(code, "requests"):
        reqs = list(code.requests(cell, seed))
    else:
        cfg, mix = cell.config, cell.traffic
        base = {"lam": cfg["lam"], "gap_target": cfg["gap_target"],
                "K": cfg["gap_rule"]["K"], "start": mix.get("start", "cold")}
        reqs = [dict(base, **entry) for entry in mix.get("schedule", [{}])]
    for r in reqs:
        if sorted(r) != sorted(REQUEST_KEYS) or r["start"] not in (
                "cold", "warm"):
            raise ValueError(f"bad request {r!r} in mix of {cell.name}")
    return reqs


def program_solver(cell: Cell, problem, seed: int) -> Callable:
    """``solve(request, start) -> (iters, converged, gap, w, alpha)``
    through the program's own entry, ``Solver.solve``, as a user calls
    it: from ``X`` on the host, paying the partitioning and transfer
    every time; ``start`` is ``(w, alpha)`` to warm-start from, or None.
    The configuration's ``solver_config`` holds further fields of the
    solver's config, passed as they are."""
    from repro.core import get_solver
    from chipbench.problem import KEY_SEED_MOD, program_input
    cfg = cell.config
    solver = get_solver(cfg["solver"])(
        engine=cfg["engine"], local_backend="pallas",
        block_format=cfg["block_format"], program_cache=True)
    X = program_input(problem)
    grid = cfg["grid"]
    confs = {}

    def solve(request, start=None):
        lam = request["lam"]
        if lam not in confs:
            confs[lam] = solver.config_cls(
                lam=lam, outer_iters=cfg["outer_iters"],
                seed=int(seed) % KEY_SEED_MOD,
                **cfg.get("solver_config", {}))
        res = solver.solve(cfg["loss"], X, problem.y, P=grid[0], Q=grid[1],
                           cfg=confs[lam], warm_start=start,
                           tol=request["gap_target"])
        return (res.iters, res.converged, res.history[-1]["duality_gap"],
                res.w, res.alpha)

    return solve


def annotate(name: str, **kw):
    import jax
    return jax.profiler.TraceAnnotation(name, **kw)


def solve_once(solve: Callable, index: int, request: dict,
               previous: Optional[SolveRecord], span: bool = True
               ) -> SolveRecord:
    """One solve, inside its ``chipbench.solve`` span where ``span`` (the
    window's solves; set-up's compile outside any span, as a user's
    first solve does); a warm request starts from ``previous``'s
    answer."""
    start = None
    if request["start"] == "warm" and previous is not None:
        start = (previous.w, previous.alpha)
    with (annotate("chipbench.solve", index=index) if span
          else contextlib.nullcontext()):
        begin = time.perf_counter()
        iters, converged, gap, w, alpha = solve(request, start)
        end = time.perf_counter()
    return SolveRecord(index, begin, end, int(iters), bool(converged),
                       float(gap), w, alpha, request)


def closed_loop(solve: Callable, reqs: List[dict], seconds: float,
                previous: Optional[SolveRecord]) -> List[SolveRecord]:
    """The mix ``{"loop": "closed", "clients": 1}``: one solve after
    another, cycling through ``reqs``, until ``seconds`` have passed
    since the first began; the last one started before that runs to its
    end.  ``previous`` is the warm-up's last solve."""
    records: List[SolveRecord] = []
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < seconds:
        i = len(records)
        previous = solve_once(solve, i, reqs[i % len(reqs)], previous)
        records.append(previous)
    return records


def window_of(cell: Cell) -> Callable:
    """The loop that drives the window: ``traffic/<traffic>.py``'s
    ``window`` (same signature as :func:`closed_loop`), or the closed
    loop of one client, which is all the mix's parameters can ask for."""
    code = cell.traffic_code
    if code is not None and hasattr(code, "window"):
        return code.window
    mix = cell.traffic
    if (mix.get("loop"), mix.get("clients")) != ("closed", 1):
        raise ValueError(f"the mix of {cell.name} needs a window of its "
                         "own in traffic/<traffic>.py")
    return closed_loop


@contextlib.contextmanager
def profiler_trace():
    """Trace what runs inside; yields a dict that holds the reduced
    :class:`~chipbench.trace_reduce.Trace` under ``"trace"`` afterwards.
    The raw trace is written under the temporary directory and removed."""
    import jax
    from chipbench import trace_reduce
    tmp = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    out: Dict = {}
    try:
        jax.profiler.start_trace(tmp, profiler_options=opts)
        try:
            with annotate("chipbench.window"):
                yield out
        finally:
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
        t1 = time.perf_counter()
        files = sorted(Path(tmp).rglob("*.xplane.pb"))
        if not files:
            raise RuntimeError("the profiler wrote no trace")
        out["trace"] = trace_reduce.load(files[-1])
        out["trace_bytes"] = sum(f.stat().st_size for f in files)
        out["seconds"] = {"stop": t1 - t0, "load": time.perf_counter() - t1}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def memory_peak_bytes(devices) -> Optional[int]:
    peaks = [d.memory_stats().get("peak_bytes_in_use")
             for d in devices if d.memory_stats()]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the check
# ---------------------------------------------------------------------------

def sample_indices(n: int, k: int, seed: int) -> List[int]:
    """``k`` of ``range(n)``, drawn from ``seed`` (all of them if fewer)."""
    rng = np.random.default_rng(int(seed) % 2 ** 63)
    return sorted(rng.choice(n, size=min(n, k), replace=False).tolist())


#: the numbers :func:`check` compares, each against the configuration's
#: ``check.<name>`` limit
CHECKED = ("unconverged", "iters_over_K", "map_err", "gap_report_err")


def check(cell: Cell, problem, records: List[SolveRecord],
          answers: Dict[int, tuple]):
    """Each number compared, with its limit, and the certificates of the
    sampled answers.  ``answers`` maps the index of each sampled solve to
    its ``(w, alpha)`` on the host.

    ``unconverged``    solves of the window that ran out of outer
                       iterations before their own gap fell below the
                       target (an answer that never came); exact;
    ``iters_over_K``   the most outer iterations a solve of the window
                       took beyond the K its request states (a gap
                       target between iterations K - 1 and K stops every
                       sound solve at K); exact: it holds the local
                       kernel's progress per iteration;
    ``map_err``        the largest distance of a sampled ``w`` from the
                       primal-dual map of its ``alpha``, relative;
    ``gap_report_err`` the largest difference between the gap a sampled
                       solve reported and its certified gap, over its
                       target.

    A converged solve reported a gap below its target, so together they
    hold every sampled certified gap below (1 + ``gap_report_err``'s
    limit) times the target.
    """
    from chipbench.reference import certify
    limits = cell.config["check"]
    certs = {i: certify(problem, w, a, lam=records[i].request["lam"],
                        loss=cell.config["loss"])
             for i, (w, a) in answers.items()}
    values = {
        "unconverged": float(sum(not r.converged for r in records)),
        "iters_over_K": float(max(r.iters - r.request["K"]
                                  for r in records)),
        "map_err": max(c["map_err"] for c in certs.values()),
        "gap_report_err": max(abs(records[i].gap - c["gap"])
                              / records[i].request["gap_target"]
                              for i, c in certs.items()),
    }
    numbers = {k: {"value": values[k], "limit": float(limits[k])}
               for k in CHECKED}
    return numbers, certs


def is_correct(numbers: Dict[str, Dict[str, float]]) -> bool:
    return bool(numbers) and all(np.isfinite(x["value"])
                                 and x["value"] <= x["limit"]
                                 for x in numbers.values())


# ---------------------------------------------------------------------------
# per-layer metrics
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric reader is given."""
    cell: Cell
    problem: object
    trace: object                     # chipbench.trace_reduce.Trace
    window: tuple                     # (start, end) ns on the trace clock
    #: (annotation event, record) of each solve inside the traced window
    solves: list
    chips: int
    peaks: dict

    @property
    def grid(self):
        return tuple(self.cell.config["grid"])

    @property
    def steps(self) -> int:
        """Coordinate steps of one cell's local epoch: the configuration's
        ``solver_config.local_steps``, else one pass over the n_p rows."""
        n_p = -(-self.problem.n // self.grid[0])
        return self.cell.config.get("solver_config", {}).get(
            "local_steps") or n_p

    @property
    def iters(self) -> int:
        return sum(r.iters for _, r in self.solves)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def read_metric(name: str, ctx: Context):
    module = importlib.import_module(f"chipbench.metrics.{name}")
    return module.read(ctx)


def device_summary(ctx: Context) -> dict:
    """``busy_s`` (mean over the chips used) and ``window_s``, and the
    breakdown: the device ops that took most time, and the longest idle
    gaps named by what the host was doing in them."""
    from chipbench import trace_reduce as tr
    lo, hi = ctx.window
    devs = ctx.trace.devices[:ctx.chips]
    busy = [tr.length(tr.clip(tr.spans(d.ops), lo, hi)) for d in devs]
    per_op: Dict[str, float] = {}
    for d in devs:
        for e in d.ops:
            if lo <= e.start < hi:
                per_op[e.op] = per_op.get(e.op, 0.0) + e.duration
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
    idle: List[tuple] = []
    if devs:
        longest = sorted(tr.gaps(tr.spans(devs[0].ops), lo, hi),
                         key=lambda g: g[0] - g[1])[:10]
        idle = [(tr.host_doing(ctx.trace, s, e) or "host", e - s)
                for s, e in longest]
    n = max(len(devs), 1)
    return {
        "busy_s": sum(busy) / n * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "breakdown": {
            "device_ops": [[k, v / n * 1e-9] for k, v in ops],
            "idle_gaps": [[k, v * 1e-9] for k, v in idle],
        },
    }


def trace_context(cell, problem, records, trace, chips, peaks) -> Context:
    """Pair each solve record with its ``chipbench.solve`` span."""
    window = trace.annotation("chipbench.window")
    if not window:
        raise RuntimeError("the trace holds no chipbench.window span")
    spans = {int(a.stats.get("index", -1)): a
             for a in trace.annotation("chipbench.solve")}
    solves = [(spans[r.index], r) for r in records if r.index in spans]
    return Context(cell=cell, problem=problem, trace=trace,
                   window=(window[0].start, window[0].end), solves=solves,
                   chips=chips, peaks=peaks)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: Cell, *, seed: int, seconds: float, trace: bool,
             devices, peaks: dict, t_start: float,
             solver_factory: Callable = program_solver,
             log=None) -> dict:
    """One run of ``cell``.  Returns the window's solve records, the
    metrics, the traced run's device summary (``extra``), the memory peak
    and the numbers compared with their limits.

    ``solver_factory(cell, problem, seed)`` gives the system under test
    (the program's ``Solver.solve``, or a stand-in for the control)."""
    from chipbench.problem import make_problem
    log = log or (lambda **kw: print(json.dumps(kw), flush=True))
    missing = [k for k in CHECKED + ("sample",)
               if k not in cell.config.get("check", {})]
    if missing:
        raise ValueError(f"the configuration of {cell.name} sets no limit "
                         f"for {missing}")
    reqs = requests(cell, seed)
    window = window_of(cell)
    problem = make_problem(cell.config, seed)
    solve = solver_factory(cell, problem, seed)
    # set-up: one whole solve of each request warms every program the
    # window runs; a warm first request runs once more, from an answer
    t = time.perf_counter()
    warm_up = reqs + reqs[:1] * (reqs[0]["start"] == "warm")
    previous = None
    with CompileCounter() as warm_compiles:
        warm_compiles.armed = True
        for i, req in enumerate(warm_up):
            previous = solve_once(solve, -1 - i, req, previous,
                                  span=False)
    warm = {"s": time.perf_counter() - t, "solves": len(warm_up),
            "iters": previous.iters, "converged": previous.converged,
            "gap": previous.gap, "compiles": warm_compiles.counts}
    setup_s = time.perf_counter() - t_start
    log(setup={"setup_s": setup_s, "warm_solve": warm})

    with CompileCounter() as compiles:
        compiles.armed = True
        if trace:
            with profiler_trace() as traced:
                records = window(solve, reqs, seconds, previous)
        else:
            records = window(solve, reqs, seconds, previous)
        compiles.armed = False
    previous = None
    peak = memory_peak_bytes(devices)
    solve_s = (records[-1].end - records[0].begin) / len(records)
    log(window={"solves": len(records), "solve_s": solve_s,
                "iters": [r.iters for r in records],
                "compiles_in_window": compiles.counts})

    metrics: Dict[str, Dict] = {}
    extra: Dict = {}
    if trace:
        t_read = time.perf_counter()
        ctx = trace_context(cell, problem, records, traced["trace"],
                            len(devices), peaks)
        notes = {}
        for m in cell.per_layer:
            value = read_metric(m["name"], ctx)
            if isinstance(value, dict):
                notes[m["name"]] = value["note"]
                value = value["value"]
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
        extra = device_summary(ctx)
        traced["seconds"]["read"] = time.perf_counter() - t_read
        log(trace={"bytes": traced["trace_bytes"],
                   "seconds": traced["seconds"],
                   "solves_traced": len(ctx.solves), "notes": notes})
    else:
        measured = {"solve_s": solve_s, "setup_s": setup_s}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": float(measured[m["name"]]),
                                  "unit": m["unit"]}

    # the reference runs once the window has closed and the memory peak
    # has been read: the sampled answers are copied to the host and the
    # program's state is let go first
    answers = {i: (np.asarray(records[i].w), np.asarray(records[i].alpha))
               for i in sample_indices(len(records),
                                       cell.config["check"]["sample"], seed)}
    for r in records:
        r.w = r.alpha = None
    del solve
    numbers, certs = check(cell, problem, records, answers)
    log(certified={str(i): c for i, c in certs.items()})
    return {"records": records, "metrics": metrics, "extra": extra,
            "memory_peak_bytes": peak, "numbers": numbers}
