"""The reduction on a small trace recorded on one TPU v5e: two D3CA
solves of two outer steps each (a dense and a sparse 4x2 grid, Pallas
kernels), inside the benchmark's spans.  Each number the metrics read is
checked against a plain count made here on a 100 ns grid from the raw
profiler events."""
import gzip
import os

import numpy as np
import pytest

from chipbench import trace_reduce as tr

DATA = os.path.join(os.path.dirname(__file__), "data", "one_chip.xplane.pb.gz")
STEP = (r"^jit_step(_fn)?(\(|$)",)
GRID_NS = 100.0


@pytest.fixture(scope="module")
def raw():
    from jax.profiler import ProfileData
    with gzip.open(DATA, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


@pytest.fixture(scope="module")
def trace(raw):
    return tr.from_profile(raw)


def raw_events(raw, line_name):
    plane = next(p for p in raw.planes if p.name == "/device:TPU:0")
    line = next(x for x in plane.lines if x.name == line_name)
    return [(e.name, float(e.start_ns), float(e.start_ns + e.duration_ns))
            for e in line.events]


def busy_grid(events, lo, hi):
    """Which 100 ns bins of [lo, hi) some event touches."""
    grid = np.zeros(int((hi - lo) / GRID_NS) + 1, bool)
    for _, s, e in events:
        a, b = max(s, lo), min(e, hi)
        if b > a:
            grid[int((a - lo) / GRID_NS):int(np.ceil((b - lo) / GRID_NS))] = 1
    return grid


class Ctx:
    def __init__(self, trace, iters):
        self.trace = trace
        w = trace.annotation("chipbench.window")[0]
        self.window = (w.start, w.end)
        self.solves = [(s, type("R", (), {"iters": iters})())
                       for s in trace.annotation("chipbench.solve")]
        self.chips = 1
        self.iters = iters * len(self.solves)


def test_structure(trace):
    assert [d.index for d in trace.devices] == [0]
    assert len(trace.annotation("chipbench.window")) == 1
    solves = trace.annotation("chipbench.solve")
    assert [s.stats["index"] for s in solves] == [0, 1]
    steps = [m for m in trace.devices[0].modules if tr.matches(m, STEP)]
    assert len(steps) == 4
    for s in solves:
        assert len([m for m in steps if s.start <= m.start < s.end]) == 2
    kernels = [e for e in trace.devices[0].ops if e.kind == "custom-call"
               and e.op.endswith("(tpu_custom_call)")]
    # the vmapped grid runs the kernel once per row of blocks (P = 4)
    assert len(kernels) == 4 * len(steps)
    assert all(any(m.start <= k.start and k.end <= m.end for m in steps)
               for k in kernels)


def test_busy_share_against_a_plain_count(raw, trace):
    from chipbench.metrics import device_idle_share
    ctx = Ctx(trace, iters=2)
    lo, hi = ctx.window
    grid = busy_grid(raw_events(raw, "XLA Ops"), lo, hi)
    plain = 100.0 * (1.0 - grid.sum() * GRID_NS / (hi - lo))
    got = device_idle_share.read(ctx)
    assert got == pytest.approx(plain, abs=0.5)
    assert 50.0 < got < 100.0


def test_gaps_between_steps_and_prep_against_a_plain_count(raw, trace):
    from chipbench.metrics import host_gap_ms, prep_ms
    ctx = Ctx(trace, iters=2)
    ops = raw_events(raw, "XLA Ops")
    mods = sorted((s, e) for name, s, e in raw_events(raw, "XLA Modules")
                  if name.startswith("jit_step("))
    idle, first = 0.0, []
    for span, _ in ctx.solves:
        inside = [m for m in mods if span.start <= m[0] < span.end]
        first.append(inside[0][0] - span.start)
        for (_, a), (b, _) in zip(inside, inside[1:]):
            idle += (~busy_grid(ops, a, b)).sum() * GRID_NS
    assert host_gap_ms.read(ctx) == pytest.approx(idle / 4 * 1e-6, rel=0.02)
    assert prep_ms.read(ctx) == pytest.approx(np.mean(first) * 1e-6)


def test_idle_gaps_are_named_by_the_host(trace):
    steps = [m for m in trace.devices[0].modules if tr.matches(m, STEP)]
    assert tr.host_doing(trace, steps[0].end, steps[1].start)
