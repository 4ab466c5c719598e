"""Telemetry subsystem tests: tracer, metrics registry, instrumentation.

Unit tests (fake clock, no jax compute) run in the default tier-1
split; the solve-under-telemetry integration tests are marked ``obs``
and get their own CI matrix leg.
"""
import json

import numpy as np
import pytest

from repro.obs import (NULL_TRACER, NullTracer, Registry, Tracer, as_tracer,
                       percentiles)
from repro.obs.metrics import DEFAULT_PERCENTILES
from repro.obs.serve import RequestMetrics
from repro.obs.trace import _NULL_SPAN


class FakeClock:
    """Deterministic clock: every call advances one second."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------- tracer ----

def test_span_nesting_and_ordering_with_fake_clock():
    tr = Tracer(clock=FakeClock())          # epoch = 1
    with tr.span("outer", which="o"):       # t0 = 2
        with tr.span("inner"):              # t0 = 3
            pass                            # t1 = 4
    # outer closes at t1 = 5

    assert [e["name"] for e in tr.events] == ["inner", "outer"]
    inner, outer = tr.events
    assert inner == {"name": "inner", "ts": 2.0, "dur": 1.0, "depth": 1,
                     "tid": inner["tid"]}
    assert outer["ts"] == 1.0 and outer["dur"] == 3.0 and outer["depth"] == 0
    assert outer["args"] == {"which": "o"}
    # the child interval nests inside the parent interval
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"]


def test_record_and_instant_and_queries():
    tr = Tracer(clock=FakeClock())          # epoch = 1
    tr.record("comm/dalpha", t0=10.0, dur=0.5, iter=3)
    tr.record("comm/dalpha", t0=10.5, dur=0.25)
    tr.instant("marker", reason="x")        # clock -> 2

    assert tr.total("comm/dalpha") == pytest.approx(0.75)
    assert len(tr.spans("comm/dalpha")) == 2
    assert tr.spans("comm/dalpha")[0]["ts"] == 9.0   # t0 - epoch
    inst = [e for e in tr.events if e["dur"] is None]
    assert len(inst) == 1 and inst[0]["name"] == "marker"
    assert inst[0]["args"] == {"reason": "x"}


def test_chrome_trace_schema_roundtrip(tmp_path):
    tr = Tracer(clock=FakeClock())
    with tr.span("solve", solver="d3ca"):
        with tr.span("step"):
            pass
    tr.instant("finish")

    path = tmp_path / "trace.json"
    tr.write_chrome_trace(str(path))
    payload = json.loads(path.read_text())

    assert set(payload) == {"traceEvents", "displayTimeUnit"}
    evs = payload["traceEvents"]
    assert len(evs) == 3
    complete = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(complete) == 2 and len(instants) == 1
    for e in complete:
        # microsecond complete events with the required keys
        assert {"name", "cat", "pid", "tid", "ts", "dur", "ph"} <= set(e)
        assert e["ts"] >= 0 and e["dur"] > 0
    assert instants[0]["s"] == "t" and "dur" not in instants[0]
    solve = next(e for e in complete if e["name"] == "solve")
    assert solve["args"] == {"solver": "d3ca"}
    # seconds -> microseconds
    assert solve["dur"] == pytest.approx(tr.spans("solve")[0]["dur"] * 1e6)


def test_jsonl_export_roundtrip(tmp_path):
    tr = Tracer(clock=FakeClock())
    with tr.span("a"):
        pass
    tr.instant("b")
    path = tmp_path / "trace.jsonl"
    tr.write_jsonl(str(path))
    lines = [json.loads(ln) for ln in path.read_text().splitlines()]
    assert lines == tr.events


def test_disabled_tracer_fast_path():
    # every disabled span() call hands back the ONE shared no-op object:
    # no per-span allocation, no event growth
    for tr in (NULL_TRACER, Tracer(enabled=False), NullTracer()):
        s1 = tr.span("a")
        s2 = tr.span("b", x=1)
        assert s1 is _NULL_SPAN and s2 is _NULL_SPAN
        with tr.span("c"):
            tr.record("d", 0.0, 1.0)
            tr.instant("e")
        assert tr.events == []
        assert not tr.enabled


def test_as_tracer_normalization():
    assert as_tracer(None) is NULL_TRACER
    tr = Tracer()
    assert as_tracer(tr) is tr


def test_tracer_is_thread_safe():
    import threading

    tr = Tracer()
    barrier = threading.Barrier(4)   # all threads alive at once, so their
                                     # idents are guaranteed distinct

    def work():
        barrier.wait()
        for i in range(50):
            with tr.span("w", i=i):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert len(tr.events) == 200
    assert len({e["tid"] for e in tr.events}) == 4
    # per-thread stacks: every span closed at depth 0
    assert all(e["depth"] == 0 for e in tr.events)


# -------------------------------------------------------------- registry ----

def test_percentiles_default_set_includes_p90():
    assert 90 in DEFAULT_PERCENTILES
    assert percentiles([]) == {"p50": 0.0, "p90": 0.0, "p99": 0.0}
    p = percentiles([1.0, 2.0, 3.0])
    assert p["p50"] == 2.0 and p["p90"] == pytest.approx(2.8)


def test_registry_get_or_create_and_snapshot():
    reg = Registry()
    c = reg.counter("serve/prefills")
    c.inc()
    c.inc(2.0)
    assert reg.counter("serve/prefills") is c        # same triple, same obj
    reg.gauge("solver/objective", solver="d3ca", engine="simulated").set(0.5)
    h = reg.histogram("solver/step_s", solver="d3ca")
    h.observe(1.0)
    h.observe(3.0)

    snap = reg.snapshot()
    assert snap["counters"] == {"serve/prefills": 3.0}
    # labels render sorted into the key
    assert snap["gauges"] == {
        "solver/objective{engine=simulated,solver=d3ca}": 0.5}
    hs = snap["histograms"]["solver/step_s{solver=d3ca}"]
    assert hs["count"] == 2 and hs["sum"] == 4.0 and hs["mean"] == 2.0
    assert hs["min"] == 1.0 and hs["max"] == 3.0
    assert {"p50", "p90", "p99"} <= set(hs)
    json.dumps(snap)                                 # plain JSON-able


def test_gauge_and_histogram_dont_collide():
    reg = Registry()
    reg.gauge("x").set(1.0)
    reg.histogram("x").observe(2.0)
    snap = reg.snapshot()
    assert snap["gauges"]["x"] == 1.0
    assert snap["histograms"]["x"]["count"] == 1


def test_registry_snapshot_matches_request_metrics_summary():
    """The serving summary and the registry snapshot are the same numbers
    bit for bit -- the legacy ServeMetrics.summary() contract, now fed
    through the registry."""
    clock = FakeClock()
    reg = Registry()
    m = RequestMetrics(clock=clock, registry=reg)
    m.prefills += 2
    m.decode_steps += 5
    m.start_request("a", n_prompt=4)     # arrival 1
    m.start_request("b", n_prompt=4)     # arrival 2
    m.start_request("c", n_prompt=4)     # arrival 3: never finishes
    m.first_token("a")                   # 4
    m.first_token("b")                   # 5
    m.finish("a", n_generated=8)         # 6
    m.finish("b", n_generated=4)         # 7

    s = m.summary()
    snap = reg.snapshot()
    assert s["requests_finished"] == 2
    assert s["requests_unfinished"] == 1     # skipped, not raised on
    assert snap["counters"]["serve/requests_finished"] == 2.0
    assert snap["counters"]["serve/generated_tokens"] == 12.0
    assert snap["counters"]["serve/prefills"] == s["prefills"] == 2
    assert snap["counters"]["serve/decode_steps"] == s["decode_steps"] == 5
    for q in ("p50", "p90", "p99"):
        assert snap["histograms"]["serve/ttft_s"][q] == s["ttft_s"][q]
        assert snap["histograms"]["serve/latency_s"][q] == s["latency_s"][q]
    assert snap["gauges"]["serve/tokens_per_sec"] == s["tokens_per_sec"]
    assert snap["gauges"]["serve/elapsed_s"] == s["elapsed_s"]


# ----------------------------------------------- solve-level integration ----

def _small_problem():
    return _case_problem("d3ca", "dense")


#: (solver, block format) on the simulated engine: every solver on dense
#: blocks, and those with a sparse path on ELL blocks as well
SOLVER_CASES = [("d3ca", "dense"), ("d3ca", "sparse"),
                ("radisa", "dense"), ("radisa", "sparse"),
                ("admm", "dense"), ("admm", "sparse"),
                ("sfk", "dense")]


def _case_problem(name, fmt):
    from repro.core import (ADMMConfig, D3CAConfig, RADiSAConfig, SFKConfig,
                            get_solver)
    from repro.data import make_svm_data
    from repro.data.sparse import make_sparse_svm_csr

    if fmt == "dense":
        X, y = make_svm_data(120, 40, seed=0)
    else:
        X, y = make_sparse_svm_csr(120, 40, density=0.2, seed=0)
    cfg = {"d3ca": D3CAConfig(lam=1e-1, outer_iters=3, local_steps=8),
           "radisa": RADiSAConfig(lam=1e-1, gamma=0.03, outer_iters=3, L=8),
           "admm": ADMMConfig(lam=1e-1, rho=1e-1, outer_iters=3),
           "sfk": SFKConfig(lam=1e-1, gamma=0.03, outer_iters=3, L=8)}[name]
    solver = get_solver(name)(engine="simulated", block_format=fmt)
    return solver, X, y, cfg


@pytest.mark.obs
@pytest.mark.parametrize("name,fmt", SOLVER_CASES)
def test_traced_solve_bit_identical_to_untraced(name, fmt):
    solver, X, y, cfg = _case_problem(name, fmt)
    plain = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg)
    traced = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg,
                          tracer=Tracer(), registry=Registry())
    assert np.array_equal(np.asarray(plain.w), np.asarray(traced.w))
    assert (plain.alpha is None) == (traced.alpha is None)
    if plain.alpha is not None:
        assert np.array_equal(np.asarray(plain.alpha),
                              np.asarray(traced.alpha))
    assert plain.history[-1]["objective"] == traced.history[-1]["objective"]


@pytest.mark.obs
@pytest.mark.parametrize("name,fmt", SOLVER_CASES)
def test_registry_solve_steps_the_program_once_an_iteration(
        name, fmt, monkeypatch):
    """A solve under a registry steps its program once an outer iteration
    and never runs the program's collective-free twin."""
    import dataclasses

    from repro.core import solver as solver_mod

    calls = {"step": 0, "local_step": 0}

    def counted(key, fn):
        def wrapped(*args):
            calls[key] += 1
            return fn(*args)
        return wrapped

    original = solver_mod.Solver.program

    def program(self, *args, **kwargs):
        prog = original(self, *args, **kwargs)
        local = prog.local_step
        return dataclasses.replace(
            prog, step=counted("step", prog.step),
            local_step=None if local is None
            else counted("local_step", local))

    monkeypatch.setattr(solver_mod.Solver, "program", program)
    solver, X, y, cfg = _case_problem(name, fmt)
    res = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg, registry=Registry())
    assert res.iters == cfg.outer_iters
    assert calls == {"step": res.iters, "local_step": 0}


def _running_sum(xs) -> float:
    """Left-to-right float sum, as a histogram accumulates (the builtin
    ``sum`` of Python 3.12 compensates, and can differ in the last bit)."""
    total = 0.0
    for x in xs:
        total += x
    return total


@pytest.mark.obs
def test_registry_snapshot_matches_solver_history():
    solver, X, y, cfg = _small_problem()
    reg = Registry()
    res = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg, registry=reg)
    snap = reg.snapshot()
    labels = "{engine=simulated,solver=d3ca}"

    # history gained the timing fields, and no modelled comm split
    for h in res.history:
        assert {"step_s", "host_s"} <= set(h)
        assert not {"local_s", "comm_s", "comm_exposed_s",
                    "comm_hidden_s"} & set(h)

    # and the registry carries the same series bit for bit
    assert snap["counters"][f"solver/iters{labels}"] == len(res.history)
    assert (snap["gauges"][f"solver/objective{labels}"]
            == res.history[-1]["objective"])
    assert (snap["gauges"][f"solver/duality_gap{labels}"]
            == res.history[-1]["duality_gap"])
    step_h = snap["histograms"][f"solver/step_s{labels}"]
    assert step_h["count"] == len(res.history)
    assert step_h["sum"] == _running_sum(h["step_s"] for h in res.history)
    host_h = snap["histograms"][f"solver/host_s{labels}"]
    assert host_h["sum"] == _running_sum(h["host_s"] for h in res.history)
    assert not [key for key in snap["histograms"]
                if key.startswith(("solver/local_s", "solver/comm_s",
                                   "solver/comm_exposed_s"))]
    assert (snap["counters"][f"solver/comm_bytes{labels}"]
            == res.comm_bytes["bytes_per_step"] * len(res.history))


def _inside(child, parent) -> bool:
    return (parent["ts"] - 1e-9 <= child["ts"] and child["ts"] + child["dur"]
            <= parent["ts"] + parent["dur"] + 1e-9)


@pytest.mark.obs
def test_trace_spans_cover_solve_wall_clock():
    """Acceptance: the emitted spans cover >= 95% of measured wall-clock
    and nest as the solver's span tree: repro.solve > repro.prep
    (partition / transfer / bind), repro.iter > repro.step /
    repro.observe > primal / dual, repro.result; a tracer synthesizes
    no spans."""
    solver, X, y, cfg = _small_problem()
    tr = Tracer()
    solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg, tracer=tr)

    (solve,) = tr.spans("repro.solve")
    assert solve["args"] == {"solver": "d3ca", "engine": "simulated"}
    covered = (tr.total("repro.prep") + tr.total("repro.iter")
               + tr.total("repro.result"))
    assert covered >= 0.95 * solve["dur"]

    (prep,) = tr.spans("repro.prep")
    for name in ("repro.prep.partition", "repro.prep.transfer",
                 "repro.prep.bind"):
        assert tr.spans(name) and all(_inside(s, prep)
                                      for s in tr.spans(name))
    # the solver's span tree and nothing else: no synthesized spans
    assert {e["name"] for e in tr.events} == {
        "repro.solve", "repro.prep", "repro.prep.partition",
        "repro.prep.transfer", "repro.prep.bind", "repro.iter",
        "repro.step", "repro.observe", "repro.observe.primal",
        "repro.observe.dual", "repro.result"}

    iters = tr.spans("repro.iter")
    assert [s["args"]["iter"] for s in iters] == list(
        range(1, cfg.outer_iters + 1))
    for it in iters:
        assert _inside(it, solve)
        t = it["args"]["iter"]
        for name in ("repro.step", "repro.observe", "repro.observe.primal",
                     "repro.observe.dual"):
            (span,) = [s for s in tr.spans(name) if s["args"]["iter"] == t]
            assert _inside(span, it)
        (obs,) = [s for s in tr.spans("repro.observe")
                  if s["args"]["iter"] == t]
        for name in ("repro.observe.primal", "repro.observe.dual"):
            (span,) = [s for s in tr.spans(name) if s["args"]["iter"] == t]
            assert _inside(span, obs)
            # the dense grid's evaluations read its device blocks
            assert span["args"]["operands"] == "blocks"
            assert span["args"]["h2d_bytes"] == 0


@pytest.mark.obs
def test_sparse_solve_evaluates_on_the_callers_csr():
    """The sparse grid keeps the solve loop's evaluation on the caller's
    CSR X: one primal and one dual span an iteration, on the host's
    operands, sending the CSR once and the labels each time."""
    from repro.core import D3CAConfig, get_solver
    from repro.data.sparse import make_sparse_svm_csr

    X, y = make_sparse_svm_csr(120, 40, density=0.2, seed=0)
    cfg = D3CAConfig(lam=1e-1, outer_iters=3, local_steps=8)
    tr = Tracer()
    get_solver("d3ca")(engine="simulated", block_format="sparse").solve(
        "hinge", X, y, P=2, Q=2, cfg=cfg, tracer=tr)
    primal = tr.spans("repro.observe.primal")
    dual = tr.spans("repro.observe.dual")
    assert [s["args"]["iter"] for s in primal] == [1, 2, 3]
    assert [s["args"]["iter"] for s in dual] == [1, 2, 3]
    assert {s["args"]["operands"] for s in primal + dual} == {"host"}
    assert [s["args"]["h2d_bytes"] for s in primal] == [
        16 * X.nnz + 4 * 120, 4 * 120, 4 * 120]
    assert [s["args"]["h2d_bytes"] for s in dual] == [4 * 120] * 3


@pytest.mark.obs
def test_untimed_solve_history_has_no_phase_fields():
    """Tracing off (the default) leaves history entries exactly as the
    legacy schema: no step_s / local_s / comm_s / host_s keys."""
    solver, X, y, cfg = _small_problem()
    res = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg)
    for h in res.history:
        assert not {"step_s", "local_s", "comm_s", "host_s"} & set(h)
