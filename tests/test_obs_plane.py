"""The live observability plane: flight recorder, health rules/monitor,
Prometheus exposition, the HTTP endpoint, and their threading through
the real services.

Unit tests (recorder ring, bundle round-trip, rule verdicts, text
format, endpoint handlers) run in the simulated leg; the tests that
drive real solves / the real ``OnlineSolverService`` carry the ``obs``
marker (the telemetry CI leg).
"""
from __future__ import annotations

import json
import math
import threading
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro.obs import (CRIT, OK, WARN, FlightRecorder, HealthMonitor,
                       HealthRule, ObsServer, Registry, as_tracer,
                       load_bundle, online_rules, parse_prometheus_text,
                       render_prometheus, rule_divergence,
                       rule_fleet_starvation, rule_gap_stall,
                       rule_queue_shed, rule_staleness, rule_version_lag)
from repro.obs.recorder import BUNDLE_SCHEMA


class FakeClock:
    """Deterministic clock: every call advances one second."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        self.t += 1.0
        return self.t


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_recorder_is_bounded_under_heavy_span_load():
    rec = FlightRecorder(capacity=64, clock=FakeClock())
    for i in range(10_000):
        with rec.span("work", i=i):
            pass
    assert len(rec.events) == 64            # ring never exceeds capacity
    assert rec.dropped == 10_000 - 64
    # drop-oldest: the tail holds the *last* spans
    kept = [e["args"]["i"] for e in rec.events]
    assert kept == list(range(10_000 - 64, 10_000))


def test_recorder_speaks_the_tracer_api():
    rec = FlightRecorder(capacity=16, clock=FakeClock())
    assert as_tracer(rec) is rec            # drop-in wherever tracer= goes
    assert rec.enabled
    with rec.span("outer", k=1):
        with rec.span("inner"):
            pass
    rec.instant("marker")
    names = [e["name"] for e in rec.events]
    assert names == ["inner", "outer", "marker"]
    assert rec.spans("outer")[0]["depth"] == 0
    assert rec.spans("inner")[0]["depth"] == 1
    # chrome-trace export works off the ring like the base class
    evs = rec.to_chrome_trace()["traceEvents"]
    assert {e["ph"] for e in evs} == {"X", "i"}


def test_recorder_capacity_validation():
    with pytest.raises(ValueError, match="capacity"):
        FlightRecorder(capacity=0)


def test_bundle_dump_roundtrips_through_loader(tmp_path):
    reg = Registry()
    reg.counter("x").inc(3)
    reg.histogram("h").observe(1.0)
    rec = FlightRecorder(capacity=8, clock=FakeClock(), registry=reg,
                         meta={"svc": "test"})
    for i in range(20):
        with rec.span("step", i=i):
            pass
    path = str(tmp_path / "bundle.json")
    rec.dump(path, reason="trigger")
    assert rec.dumps == [path]

    b = load_bundle(path)                   # validates schema + trace
    assert b["schema"] == BUNDLE_SCHEMA
    assert b["reason"] == "trigger"
    assert b["meta"]["svc"] == "test"
    assert b["capacity"] == 8
    assert b["retained_events"] == 8
    assert b["dropped_events"] == 12
    assert len(b["trace"]["traceEvents"]) == 8
    assert b["metrics"]["counters"]["x"] == 3
    assert b["metrics"]["histograms"]["h"]["count"] == 1


def test_load_bundle_rejects_foreign_and_malformed(tmp_path):
    p = tmp_path / "notabundle.json"
    p.write_text(json.dumps({"schema": "something/else"}))
    with pytest.raises(ValueError, match="schema"):
        load_bundle(str(p))
    p.write_text(json.dumps({"schema": BUNDLE_SCHEMA, "trace": {}}))
    with pytest.raises(ValueError, match="traceEvents"):
        load_bundle(str(p))
    p.write_text(json.dumps({
        "schema": BUNDLE_SCHEMA,
        "trace": {"traceEvents": [{"ph": "B", "name": "x"}]}}))
    with pytest.raises(ValueError, match="phase"):
        load_bundle(str(p))


def test_crash_guard_dumps_and_reraises(tmp_path):
    rec = FlightRecorder(capacity=8, clock=FakeClock())
    path = str(tmp_path / "crash.json")
    with pytest.raises(RuntimeError, match="boom"):
        with rec.crash_guard(path):
            with rec.span("doomed"):
                pass
            raise RuntimeError("boom")
    b = load_bundle(path)
    assert b["reason"] == "crash:RuntimeError"
    assert [e["name"] for e in b["trace"]["traceEvents"]] == ["doomed"]


# ---------------------------------------------------------------------------
# histogram reservoir (bounded memory)
# ---------------------------------------------------------------------------

def test_histogram_reservoir_exact_below_cap():
    from repro.obs.metrics import Histogram, percentiles
    h = Histogram(cap=100)
    xs = [float(i) for i in range(100)]
    for v in xs:
        h.observe(v)
    s = h.summary()
    # below the cap the reservoir IS the series: summaries bit-identical
    assert s["count"] == 100 and s["sum"] == sum(xs)
    assert s["min"] == 0.0 and s["max"] == 99.0
    assert {k: v for k, v in s.items() if k.startswith("p")} \
        == percentiles(xs)
    assert h.observations == xs             # arrival order preserved


def test_histogram_reservoir_bounded_above_cap():
    from repro.obs.metrics import Histogram
    h = Histogram(cap=64)
    n = 10_000
    for i in range(n):
        h.observe(float(i))
    assert len(h.observations) == 64        # memory capped
    assert h.count == n                     # aggregates stay exact
    assert h.sum == sum(range(n))
    s = h.summary()
    assert s["min"] == 0.0 and s["max"] == float(n - 1)
    assert s["mean"] == sum(range(n)) / n
    # the reservoir is a uniform sample: p50 lands near the true median
    assert abs(s["p50"] - (n - 1) / 2) < 0.25 * n
    assert all(0.0 <= v <= n - 1 for v in h.observations)


def test_histogram_reservoir_deterministic():
    from repro.obs.metrics import Histogram
    a, b = Histogram(cap=32), Histogram(cap=32)
    for i in range(1000):
        a.observe(float(i))
        b.observe(float(i))
    assert a.observations == b.observations   # seeded PRNG, no flake


def test_registry_histogram_cap_kwarg():
    reg = Registry()
    h = reg.histogram("svc/lat_s", cap=16)
    for i in range(100):
        h.observe(float(i))
    assert len(h.observations) == 16
    assert reg.snapshot()["histograms"]["svc/lat_s"]["count"] == 100


def test_histogram_cap_validation():
    from repro.obs.metrics import Histogram
    with pytest.raises(ValueError, match="cap"):
        Histogram(cap=0)


# ---------------------------------------------------------------------------
# registry under concurrency
# ---------------------------------------------------------------------------

def test_registry_concurrent_writers_lose_no_updates():
    """The online service scores and publishes from different threads
    while the endpoint snapshots from a third: counters must not lose
    increments, histogram count/sum must stay exact, and every snapshot
    taken mid-flight must be self-consistent."""
    reg = Registry()
    n_threads, n_ops = 8, 2_000
    snap_errors = []
    stop = threading.Event()

    def writer(tid):
        c = reg.counter("c")                # all threads share one counter
        g = reg.gauge("g", t=str(tid))
        h = reg.histogram("h")
        for i in range(n_ops):
            c.inc()
            g.set(float(i))
            h.observe(1.0)

    def snapshotter():
        while not stop.is_set():
            s = reg.snapshot()
            h = s["histograms"].get("h")
            if h is None:
                continue
            # self-consistency: aggregates move together under the
            # histogram lock -- sum must equal count for unit observes
            if h["sum"] != float(h["count"]):
                snap_errors.append((h["count"], h["sum"]))

    threads = [threading.Thread(target=writer, args=(t,))
               for t in range(n_threads)]
    snapper = threading.Thread(target=snapshotter)
    snapper.start()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    stop.set()
    snapper.join()

    total = n_threads * n_ops
    assert reg.counter("c").value == total  # no lost increments
    h = reg.histogram("h")
    assert h.count == total and h.sum == float(total)
    assert snap_errors == []                # every snapshot consistent


# ---------------------------------------------------------------------------
# health rules
# ---------------------------------------------------------------------------

def _reg_with(gauges=(), counters=(), hists=()):
    reg = Registry()
    for name, labels, v in gauges:
        reg.gauge(name, **labels).set(v)
    for name, labels, v in counters:
        reg.counter(name, **labels).inc(v)
    for name, labels, vs in hists:
        h = reg.histogram(name, **labels)
        for v in vs:
            h.observe(v)
    return reg


def test_rule_divergence_nan_is_crit():
    rule = rule_divergence()
    reg = _reg_with(gauges=[("solver/objective", {"solver": "d3ca"},
                             float("nan"))])
    status, msg, _ = rule.check(reg.snapshot())
    assert status == CRIT and "non-finite" in msg


def test_rule_divergence_stall_is_warn():
    rule = rule_divergence(window=3)
    reg = Registry()
    g = reg.gauge("solver/rel_opt")
    # improving: stays OK
    for v in (1.0, 0.5, 0.25, 0.12, 0.06):
        g.set(v)
        status, _, _ = rule.check(reg.snapshot())
        assert status == OK
    # frozen: WARN once the window fills with non-improvement
    statuses = []
    for _ in range(4):
        statuses.append(rule.check(reg.snapshot())[0])
    assert statuses[-1] == WARN


def test_rule_gap_stall_and_growth():
    rule = rule_gap_stall(window=3)
    reg = Registry()
    g = reg.gauge("solver/duality_gap")
    for v in (1.0, 0.5, 0.2, 0.1):          # shrinking: OK
        g.set(v)
        assert rule.check(reg.snapshot())[0] == OK
    for v in (0.1, 0.1, 0.1):               # stalled: WARN
        g.set(v)
        last = rule.check(reg.snapshot())[0]
    assert last == WARN
    for v in (0.2, 0.5, 1.0, 2.0):          # growing: CRIT
        g.set(v)
        last = rule.check(reg.snapshot())[0]
    assert last == CRIT


def test_rule_staleness_thresholds():
    rule = rule_staleness(10.0)
    snap = lambda v: _reg_with(                      # noqa: E731
        gauges=[("online/staleness_s", {}, v)]).snapshot()
    assert rule.check(snap(1.0))[0] == OK
    assert rule.check(snap(6.0))[0] == WARN
    assert rule.check(snap(11.0))[0] == CRIT
    assert rule.check(Registry().snapshot())[0] == OK   # no series yet


def test_rule_version_lag_thresholds():
    rule = rule_version_lag(100)
    snap = lambda v: _reg_with(                      # noqa: E731
        gauges=[("online/version_lag", {}, v)]).snapshot()
    assert rule.check(snap(10))[0] == OK
    assert rule.check(snap(60))[0] == WARN
    assert rule.check(snap(101))[0] == CRIT


def test_rule_queue_shed_uses_deltas_between_evaluations():
    rule = rule_queue_shed(max_rate=0.2)
    reg = Registry()
    adm = reg.counter("online/ingested")
    rej = reg.counter("online/rejected")
    adm.inc(100)
    assert rule.check(reg.snapshot())[0] == OK
    # next interval: 50 offered, 30 shed -> 60% > 20% -> CRIT
    adm.inc(20)
    rej.inc(30)
    assert rule.check(reg.snapshot())[0] == CRIT
    # following interval: healthy again (deltas, not cumulative rate)
    adm.inc(100)
    status, _, rate = rule.check(reg.snapshot())
    assert status == OK and rate == 0.0
    # idle interval: no traffic is OK, not a division by zero
    assert rule.check(reg.snapshot())[0] == OK


def test_rule_fleet_starvation():
    rule = rule_fleet_starvation(min_tenants=2)
    reg = _reg_with(gauges=[("fleet/bucket_tenants", {"bucket": "a"}, 4),
                            ("fleet/bucket_tenants", {"bucket": "b"}, 1)])
    status, msg, v = rule.check(reg.snapshot())
    assert status == WARN and v == 1
    reg2 = _reg_with(gauges=[("fleet/bucket_tenants", {"bucket": "a"}, 4)])
    assert rule.check(reg2.snapshot())[0] == OK


def test_broken_rule_degrades_to_warn_not_crash():
    def boom(snap):
        raise KeyError("broken rule")
    mon = HealthMonitor(Registry(), [HealthRule("bad", boom)],
                        clock=FakeClock())
    [ev] = mon.evaluate()
    assert ev.status == WARN and "rule error" in ev.message


# ---------------------------------------------------------------------------
# health monitor: verdict recording + edge-triggered dumps
# ---------------------------------------------------------------------------

def test_monitor_records_verdicts_into_registry():
    reg = Registry()
    reg.gauge("online/staleness_s").set(1.0)
    mon = HealthMonitor(reg, [rule_staleness(10.0)], clock=FakeClock())
    mon.evaluate()
    snap = reg.snapshot()
    assert snap["gauges"]["health/status{rule=staleness}"] == 0
    assert snap["gauges"]["health/overall"] == 0
    reg.gauge("online/staleness_s").set(99.0)
    mon.evaluate()
    snap = reg.snapshot()
    assert snap["gauges"]["health/status{rule=staleness}"] == 2
    assert snap["gauges"]["health/overall"] == 2
    assert snap["counters"][
        "health/transitions{rule=staleness,status=crit}"] == 1
    assert mon.status == CRIT


def test_monitor_fires_exactly_one_dump_per_crit_edge(tmp_path):
    reg = Registry()
    reg.gauge("online/staleness_s").set(1.0)
    rec = FlightRecorder(capacity=8, clock=FakeClock(), registry=reg)
    mon = HealthMonitor(reg, [rule_staleness(10.0)], recorder=rec,
                        dump_dir=str(tmp_path), clock=FakeClock())
    mon.evaluate()
    assert rec.dumps == []                  # healthy: no dump
    reg.gauge("online/staleness_s").set(99.0)
    for _ in range(5):                      # stays CRIT across evals
        mon.evaluate()
    assert len(rec.dumps) == 1              # edge-triggered, not level
    b = load_bundle(rec.dumps[0])
    assert b["reason"].startswith("health:staleness")
    # recovery re-arms the edge: a second breach dumps again
    reg.gauge("online/staleness_s").set(1.0)
    mon.evaluate()
    reg.gauge("online/staleness_s").set(99.0)
    mon.evaluate()
    assert len(rec.dumps) == 2


def test_monitor_poll_rate_limit():
    reg = Registry()
    calls = []

    def probe(snap):
        calls.append(1)
        return OK, "ok", None

    clock = FakeClock()                     # +1s per reading
    mon = HealthMonitor(reg, [HealthRule("probe", probe)],
                        min_interval_s=10.0, clock=clock)
    for _ in range(8):
        mon.poll()
    # 8 polls over ~16 fake seconds with a 10 s interval -> ~2 evals
    assert 1 <= len(calls) < 8


def test_monitor_healthz_payload():
    reg = Registry()
    reg.gauge("online/staleness_s").set(99.0)
    mon = HealthMonitor(reg, [rule_staleness(10.0)], clock=FakeClock())
    hz = mon.healthz()
    assert hz["status"] == CRIT
    assert hz["rules"]["staleness"]["status"] == CRIT
    assert "99.000s" in hz["rules"]["staleness"]["message"]


# ---------------------------------------------------------------------------
# prometheus exposition
# ---------------------------------------------------------------------------

def test_render_prometheus_counters_gauges_histograms():
    reg = Registry()
    reg.counter("solver/iters", solver="d3ca", engine="simulated").inc(5)
    reg.gauge("solver/objective", solver="d3ca").set(0.25)
    h = reg.histogram("solver/step_s", solver="d3ca")
    for v in (0.1, 0.2, 0.3):
        h.observe(v)
    text = render_prometheus(reg.snapshot())
    assert "# TYPE solver_iters counter" in text
    assert 'solver_iters{engine="simulated",solver="d3ca"} 5.0' in text
    assert "# TYPE solver_step_s summary" in text
    assert 'quantile="0.5"' in text
    assert 'solver_step_s_count{solver="d3ca"} 3.0' in text
    parsed = parse_prometheus_text(text)    # the format self-validates
    assert parsed["solver_objective"][
        frozenset({("solver", "d3ca")})] == 0.25
    assert parsed["solver_step_s_sum"][
        frozenset({("solver", "d3ca")})] == pytest.approx(0.6)


def test_render_prometheus_nonfinite_values():
    reg = Registry()
    reg.gauge("w_norm").set(float("nan"))
    reg.gauge("peak").set(float("inf"))
    text = render_prometheus(reg.snapshot())
    parsed = parse_prometheus_text(text)
    assert math.isnan(parsed["w_norm"][frozenset()])
    assert math.isinf(parsed["peak"][frozenset()])


def test_render_prometheus_sanitizes_names_and_escapes_labels():
    reg = Registry()
    reg.counter("compress/ef_norm/w-contrib", codec='top"k').inc()
    text = render_prometheus(reg.snapshot(), prefix="repro_")
    assert "repro_compress_ef_norm_w_contrib" in text
    parsed = parse_prometheus_text(text)
    [(labels, v)] = list(
        parsed["repro_compress_ef_norm_w_contrib"].items())
    assert ("codec", 'top"k') in labels and v == 1.0


def test_parse_prometheus_rejects_garbage():
    with pytest.raises(ValueError, match="not a valid sample"):
        parse_prometheus_text("this is { not metrics")
    with pytest.raises(ValueError, match="bad value"):
        parse_prometheus_text("ok_name twelve")


def test_render_empty_registry_is_valid():
    text = render_prometheus(Registry().snapshot())
    assert parse_prometheus_text(text) == {}


# ---------------------------------------------------------------------------
# HTTP endpoint
# ---------------------------------------------------------------------------

def _get(url):
    with urllib.request.urlopen(url, timeout=10) as resp:
        return resp.status, resp.read().decode()


def test_obs_server_serves_metrics_healthz_varz():
    reg = Registry()
    reg.counter("online/ingested").inc(7)
    reg.gauge("online/staleness_s").set(1.0)
    mon = HealthMonitor(reg, [rule_staleness(10.0)], clock=FakeClock())
    rec = FlightRecorder(capacity=8, clock=FakeClock())
    with ObsServer(reg, monitor=mon, recorder=rec, port=0) as srv:
        assert srv.port != 0                # ephemeral port resolved

        code, body = _get(srv.url + "/metrics")
        assert code == 200
        parsed = parse_prometheus_text(body)     # valid text format
        assert parsed["online_ingested"][frozenset()] == 7.0
        # the monitor's own verdicts are scrapeable too (after healthz)
        code, body = _get(srv.url + "/healthz")
        assert code == 200
        assert json.loads(body)["status"] == "ok"

        code, body = _get(srv.url + "/varz")
        varz = json.loads(body)
        assert varz["metrics"]["counters"]["online/ingested"] == 7.0
        assert varz["recorder"]["capacity"] == 8
        assert varz["uptime_s"] >= 0

        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/nope")
        assert ei.value.code == 404


def test_obs_server_healthz_503_on_crit():
    reg = Registry()
    reg.gauge("online/staleness_s").set(999.0)
    mon = HealthMonitor(reg, [rule_staleness(10.0)], clock=FakeClock())
    with ObsServer(reg, monitor=mon, port=0) as srv:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _get(srv.url + "/healthz")
        assert ei.value.code == 503         # probes need no body parsing
        assert json.loads(ei.value.read().decode())["status"] == "crit"
        # /metrics keeps serving while unhealthy
        code, body = _get(srv.url + "/metrics")
        assert code == 200
        parse_prometheus_text(body)


def test_obs_server_without_monitor_reports_ok():
    with ObsServer(Registry(), port=0) as srv:
        code, body = _get(srv.url + "/healthz")
        assert code == 200 and json.loads(body)["status"] == "ok"


# ---------------------------------------------------------------------------
# launch helper
# ---------------------------------------------------------------------------

def test_parse_listen_forms():
    from repro.launch.obs import parse_listen
    assert parse_listen("0.0.0.0:9100") == ("0.0.0.0", 9100)
    assert parse_listen(":0") == ("127.0.0.1", 0)
    with pytest.raises(ValueError, match="HOST:PORT"):
        parse_listen("nope")


def test_build_plane_wires_recorder_monitor_server(tmp_path):
    import argparse

    from repro.launch.obs import build_plane
    args = argparse.Namespace(
        listen="127.0.0.1:0", health=True,
        flight_recorder=str(tmp_path / "b.json"), flight_capacity=32)
    plane = build_plane(args, rules=online_rules(), start_server=False)
    assert plane.active
    assert plane.recorder.capacity == 32
    assert plane.monitor.recorder is plane.recorder
    assert plane.monitor.dump_dir == str(tmp_path)
    assert plane.server is not None and plane.server.port == 0
    assert plane.tracer_or(None) is plane.recorder
    sentinel = object()
    assert plane.tracer_or(sentinel) is sentinel

    out = plane.finalize()
    assert out["flight_recorder"]["bundle"] == str(tmp_path / "b.json")
    assert load_bundle(str(tmp_path / "b.json"))["reason"] == "exit"


def test_build_plane_inactive_without_flags():
    import argparse

    from repro.launch.obs import build_plane
    args = argparse.Namespace(listen=None, health=False,
                              flight_recorder=None, flight_capacity=None)
    plane = build_plane(args)
    assert not plane.active
    assert plane.crash_guard() is not None  # still a usable no-op guard
    with plane.crash_guard():
        pass
    assert plane.finalize() == {}


# ---------------------------------------------------------------------------
# threading through the real stack (obs CI leg)
# ---------------------------------------------------------------------------

def _small_problem():
    from repro.core import D3CAConfig, get_solver
    from repro.data import make_svm_data
    X, y = make_svm_data(120, 40, seed=0)
    cfg = D3CAConfig(lam=1e-1, outer_iters=4, local_steps=8)
    return get_solver("d3ca")(engine="simulated"), X, y, cfg


@pytest.mark.obs
def test_live_endpoint_does_not_perturb_solve():
    """/metrics scraped concurrently while a solve runs: the text stays
    valid Prometheus throughout, and the solve's iterates/objective
    series are bit-identical to the same solve without the endpoint."""
    solver, X, y, cfg = _small_problem()
    reg_off = Registry()
    plain = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg,
                         registry=reg_off)

    reg_on = Registry()
    stop = threading.Event()
    scrapes, parse_errors = [], []
    with ObsServer(reg_on, port=0) as srv:
        def scraper():
            while not stop.is_set():
                try:
                    _, body = _get(srv.url + "/metrics")
                    parse_prometheus_text(body)
                    scrapes.append(len(body))
                except Exception as e:      # pragma: no cover - fail below
                    parse_errors.append(repr(e))
        t = threading.Thread(target=scraper)
        t.start()
        try:
            live = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg,
                                registry=reg_on)
        finally:
            stop.set()
            t.join()

    assert parse_errors == []
    assert len(scrapes) > 0                 # the endpoint really ran
    assert np.array_equal(np.asarray(plain.w), np.asarray(live.w))
    assert ([h["objective"] for h in plain.history]
            == [h["objective"] for h in live.history])
    assert ([h["duality_gap"] for h in plain.history]
            == [h["duality_gap"] for h in live.history])


@pytest.mark.obs
def test_solve_with_recorder_and_monitor_stays_ok(tmp_path):
    """A healthy solve under the full plane: recorder ring bounded, all
    rules OK end-to-end, no dumps fired."""
    from repro.obs import solver_rules
    solver, X, y, cfg = _small_problem()
    reg = Registry()
    rec = FlightRecorder(capacity=32, registry=reg)
    mon = HealthMonitor(reg, solver_rules(), recorder=rec,
                        dump_dir=str(tmp_path))
    res = solver.solve("hinge", X, y, P=2, Q=2, cfg=cfg, tracer=rec,
                       registry=reg, monitor=mon)
    assert res.iters == cfg.outer_iters
    assert mon.status == OK
    assert mon.evaluations >= cfg.outer_iters   # polled every iteration
    assert rec.dumps == []
    assert len(rec.events) <= 32
    snap = reg.snapshot()
    assert snap["gauges"]["health/overall"] == 0


def _online_service(monitor_rules, queue_capacity=4096, clock=None,
                    dump_dir=None):
    from repro.core import D3CAConfig
    from repro.online import OnlineConfig, OnlineSolverService
    reg = Registry()
    rec = FlightRecorder(capacity=64, registry=reg)
    mon = HealthMonitor(reg, monitor_rules, recorder=rec,
                        dump_dir=dump_dir)
    cfg = OnlineConfig(m=10, capacity=32, P=2, Q=2,
                       solver_cfg=D3CAConfig(lam=1e-2, local_steps=8),
                       passes=2, queue_capacity=queue_capacity)
    kw = {} if clock is None else {"clock": clock}
    svc = OnlineSolverService(cfg, registry=reg, monitor=mon, **kw)
    return svc, reg, rec, mon


def _stream(b, m, seed=0):
    rng = np.random.default_rng(seed)
    X = rng.normal(size=(b, m)).astype(np.float32)
    y = np.sign(X @ np.linspace(-1, 1, m) + 0.1).astype(np.float32)
    return X, np.where(y == 0, 1.0, y)


@pytest.mark.obs
def test_online_service_healthy_run_stays_ok(tmp_path):
    svc, reg, rec, mon = _online_service(
        online_rules(max_staleness_s=1e6, max_shed_rate=0.5),
        dump_dir=str(tmp_path))
    for i in range(3):
        svc.submit(*_stream(8, 10, seed=i))
        svc.run_pending()
        svc.score(_stream(16, 10, seed=100 + i)[0])
    assert mon.status == OK
    assert mon.evaluations > 0
    assert rec.dumps == []
    # the service published its w_norm sentinel for the divergence rule
    g = {k.split("{")[0]: v for k, v in reg.snapshot()["gauges"].items()}
    assert math.isfinite(g["online/w_norm"]) and g["online/w_norm"] > 0


@pytest.mark.obs
def test_online_divergence_flips_crit_and_dumps_once(tmp_path):
    """Injected NaN model (a diverged update) through the real publish
    path: the divergence rule flips /healthz to CRIT and fires exactly
    one postmortem dump."""
    svc, reg, rec, mon = _online_service(
        online_rules(max_staleness_s=1e6), dump_dir=str(tmp_path))
    svc.submit(*_stream(8, 10))
    svc.run_pending()
    assert mon.status == OK

    # corrupt the next update's result: real solver, poisoned output
    real_update = svc.solver.update

    def poisoned(*a, **kw):
        res = real_update(*a, **kw)
        import dataclasses as dc
        return dc.replace(res, w=np.full_like(np.asarray(res.w),
                                              np.nan))
    svc.solver.update = poisoned
    svc.submit(*_stream(8, 10, seed=1))
    svc.run_pending()                       # publishes NaN w -> NaN norm

    assert mon.status == CRIT
    hz = mon.healthz(evaluate=False)
    assert hz["rules"]["online_divergence"]["status"] == CRIT
    assert len(rec.dumps) == 1              # exactly one bundle
    # staying diverged across further activity does not re-dump
    svc.score(_stream(8, 10)[0])
    mon.evaluate()
    assert len(rec.dumps) == 1
    b = load_bundle(rec.dumps[0])
    assert b["reason"].startswith("health:online_divergence")
    assert not math.isfinite(
        {k.split("{")[0]: v
         for k, v in b["metrics"]["gauges"].items()}["online/w_norm"])


@pytest.mark.obs
def test_online_staleness_breach_flips_crit_and_dumps_once(tmp_path):
    clock = FakeClock()
    svc, reg, rec, mon = _online_service(
        online_rules(max_staleness_s=30.0), clock=clock,
        dump_dir=str(tmp_path))
    svc.submit(*_stream(8, 10))
    svc.run_pending()
    assert mon.status == OK
    # the fake clock advances 1 s per reading: keep scoring without an
    # update until the served snapshot ages past the breach
    for i in range(60):
        svc.score(_stream(4, 10, seed=i)[0])
    assert mon.status == CRIT
    assert mon.healthz(evaluate=False)["rules"]["staleness"]["status"] \
        == CRIT
    assert len(rec.dumps) == 1
    assert load_bundle(rec.dumps[0])["reason"] \
        .startswith("health:staleness")


@pytest.mark.obs
def test_online_queue_saturation_flips_crit_and_dumps_once(tmp_path):
    from repro.online import QueueFullError
    svc, reg, rec, mon = _online_service(
        online_rules(max_staleness_s=1e6, max_shed_rate=0.2),
        queue_capacity=8, dump_dir=str(tmp_path))
    svc.submit(*_stream(8, 10))             # fills the queue
    with pytest.raises(QueueFullError):
        svc.submit(*_stream(8, 10, seed=1))  # 8/16 offered shed -> 50%
    assert mon.status == CRIT
    assert mon.healthz(evaluate=False)["rules"]["queue_shed"]["status"] \
        == CRIT
    assert len(rec.dumps) == 1
    assert load_bundle(rec.dumps[0])["reason"] \
        .startswith("health:queue_shed")
    # draining recovers: the shed-rate delta window sees clean traffic
    svc.run_pending()
    svc.submit(*_stream(4, 10, seed=2))
    assert mon.status == OK
    assert len(rec.dumps) == 1
