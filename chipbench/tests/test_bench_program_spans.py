"""The program's own spans in a reduced trace: the innermost-span
arithmetic and the four readers of ``repro.*`` spans on hand-made events
whose answers are counted by hand; on the existing one-chip recording,
where the program wrote no spans (as at a parent that has none), they
find nothing; on a recording with the program's spans each equals a
plain count from the raw events."""
import gzip
import os

import numpy as np
import pytest

from chipbench import program_spans as ps
from chipbench import trace_reduce as tr
from chipbench.metrics import bind_ms, observe_ms, partition_ms, transfer_ms
from chipbench.trace_reduce import Device, Event, Trace

DATA = os.path.join(os.path.dirname(__file__), "data")
KERNEL = ('%closed_call.9 = (f32[2,128]{1,0}) custom-call(s32[128]{0} %a), '
          'custom_call_target="tpu_custom_call"')


def ev(name, start, end, **stats):
    return Event(name, float(start), float(end), stats)


def one_solve_trace():
    """Window 0-1000 ns, one solve 0-1000: prep 10-200 (a cut 10-110 that
    holds a send 60-90, a send 110-140, a bind 140-200), two outer steps
    whose modules run 220-300 and 520-600 on the device, each observed
    (250-450 and 550-900) with a primal and a dual evaluation; the device
    runs an objective product 320-380 inside the first observation."""
    host = [ev("PjitFunction(step)", 205, 210)]
    program = [
        ev("repro.solve", 5, 950),
        ev("repro.prep", 10, 200),
        ev("repro.prep.partition", 10, 110),
        ev("repro.prep.transfer", 60, 90),
        ev("repro.prep.transfer", 110, 140),
        ev("repro.prep.bind", 140, 200),
        ev("repro.iter", 200, 450), ev("repro.step", 200, 250),
        ev("repro.observe", 250, 450),
        ev("repro.observe.primal", 260, 390),
        ev("repro.observe.dual", 390, 440),
        ev("repro.iter", 500, 900), ev("repro.step", 500, 550),
        ev("repro.observe", 550, 900),
        ev("repro.observe.primal", 560, 700),
        ev("repro.observe.dual", 700, 890),
        ev("repro.result", 900, 940),
    ]
    ops = [ev(KERNEL, 220, 300), ev("%fusion.3 = f32[8]{0} fusion(%a)",
                                    320, 380), ev(KERNEL, 520, 600)]
    mods = [ev("jit_step(1)", 220, 300), ev("jit_fusion(2)", 320, 380),
            ev("jit_step(1)", 520, 600)]
    annotations = [ev("chipbench.window", 0, 1000),
                   ev("chipbench.solve", 0, 1000, index=0)]
    host = sorted(host + program, key=lambda e: e.start)
    return Trace([Device(0, ops, mods)], annotations, host)


class Ctx:
    def __init__(self, trace):
        self.trace = trace
        w = trace.annotation("chipbench.window")[0]
        self.window = (w.start, w.end)
        self.solves = [(s, None) for s in trace.annotation("chipbench.solve")]
        self.chips = 1


def test_innermost_pieces_cut_nested_spans():
    spans = [ev("a", 0, 100), ev("b", 10, 40), ev("c", 20, 30),
             ev("d", 40, 60), ev("e", 200, 210)]
    got = [(s, e, span.name) for s, e, span in ps.innermost(spans)]
    assert got == [(0, 10, "a"), (10, 20, "b"), (20, 30, "c"), (30, 40, "b"),
                   (40, 60, "d"), (60, 100, "a"), (200, 210, "e")]
    pieces = ps.innermost(spans)
    assert ps.covering(pieces, 25, 205) == {"c": 5, "b": 10, "d": 20,
                                            "a": 40, None: 100, "e": 5}


def test_idle_by_innermost_span_and_self_time():
    t = one_solve_trace()
    idle = ps.idle_by_span(t, t.devices[0], [(0, 1000)])
    # device busy 220-300, 320-380, 520-600: idle 0-220, 300-320,
    # 380-520, 600-1000, split by the innermost span on the host
    assert idle == {None: 5 + 50, "repro.solve": 5 + 50 + 10,
                    "repro.prep.partition": 50 + 20,
                    "repro.prep.transfer": 30 + 30, "repro.prep.bind": 60,
                    "repro.step": 20 + 20,
                    "repro.observe.primal": 20 + 10 + 100,
                    "repro.observe.dual": 50 + 190,
                    "repro.observe": 10 + 10, "repro.result": 40}
    assert sum(idle.values()) == 1000 - 80 - 60 - 80
    (cut,) = ps.events(t, name="repro.prep.partition")
    assert ps.self_time(cut, ps.events(t, name="repro.prep.transfer")) == 70


def test_prep_readers_by_hand():
    ctx = Ctx(one_solve_trace())
    assert partition_ms.read(ctx) == pytest.approx(70e-6)
    assert transfer_ms.read(ctx) == pytest.approx(60e-6)
    assert bind_ms.read(ctx) == pytest.approx(60e-6)


def test_observe_reader_by_hand():
    got = observe_ms.read(Ctx(one_solve_trace()))
    # iteration 1: its step ends at 300, the observation at 450: 150 ns
    # held, 60 of them busy (320-380); iteration 2: 600 to 900, all idle
    assert got["value"] == pytest.approx((150 + 300) / 2 * 1e-6)
    assert got["note"] == pytest.approx({
        "device_busy_ms": 60 / 2 * 1e-6, "device_idle_ms": 390 / 2 * 1e-6,
        "primal_ms": (130 + 140) / 2 * 1e-6,
        "dual_ms": (50 + 190) / 2 * 1e-6})


def test_readers_find_nothing_without_program_spans():
    t = one_solve_trace()
    bare = Trace(t.devices, t.annotations,
                 [e for e in t.host if not e.name.startswith("repro.")])
    for reader in (partition_ms, transfer_ms, bind_ms, observe_ms):
        assert reader.read(Ctx(bare)) is None


def test_recording_without_program_spans_reads_nothing():
    """The existing recording predates the program's spans, as a parent
    run does: its reduction holds no ``repro.`` events and the four
    readers leave their metrics out."""
    from jax.profiler import ProfileData
    with gzip.open(os.path.join(DATA, "one_chip.xplane.pb.gz"), "rb") as f:
        trace = tr.from_profile(ProfileData.from_serialized_xspace(f.read()))
    assert trace.host and not ps.events(trace)
    for reader in (partition_ms, transfer_ms, bind_ms, observe_ms):
        assert reader.read(Ctx(trace)) is None


# ---------------------------------------------------------------------------
# a recording from one TPU v5e (chipbench/tests/record_spans.py): two
# solves, dense then sparse, of two outer steps each, with the program's
# spans; each reader against a plain count from the raw events
# ---------------------------------------------------------------------------

GRID_NS = 100.0


@pytest.fixture(scope="module")
def spans_raw():
    from jax.profiler import ProfileData
    path = os.path.join(DATA, "spans_one_chip.xplane.pb.gz")
    with gzip.open(path, "rb") as f:
        return ProfileData.from_serialized_xspace(f.read())


def raw_line(raw, plane_prefix, line_name=None, event_prefix=None):
    """(name, start, end) of the events of the first matching line."""
    for plane in raw.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            names = [e.name for e in line.events]
            if (line_name is None or line.name == line_name) and (
                    event_prefix is None
                    or any(n.startswith(event_prefix) for n in names)):
                return [(e.name, float(e.start_ns),
                         float(e.start_ns + e.duration_ns))
                        for e in line.events]
    raise LookupError(plane_prefix)


def test_recorded_spans_nest_in_each_solve(spans_raw):
    trace = tr.from_profile(spans_raw)
    solves = trace.annotation("chipbench.solve")
    assert len(solves) == 2
    for s in solves:
        inside = [e.name for e in ps.events(trace, s.start, s.end)]
        assert inside.count("repro.solve") == 1
        assert inside.count("repro.iter") == 2
        assert inside.count("repro.observe.primal") == 2
        assert inside.count("repro.prep.bind") == 1
    # the readers read the same number of traced solves
    assert partition_ms.read(Ctx(trace)) is not None


def test_recorded_readers_against_a_plain_count(spans_raw):
    trace = tr.from_profile(spans_raw)
    ctx = Ctx(trace)
    host = raw_line(spans_raw, "/host:", event_prefix="chipbench.")
    solves = [(s, e) for n, s, e in host if n == "chipbench.solve"]

    def named(name, lo, hi):
        return [(s, e) for n, s, e in host if n == name and lo <= s < hi]

    cut, send, bind = [], [], []
    for lo, hi in solves:
        sends = named("repro.prep.transfer", lo, hi)
        cut.append(sum(e - s - sum(max(0.0, min(e, b) - max(s, a))
                                   for a, b in sends)
                       for s, e in named("repro.prep.partition", lo, hi)))
        send.append(sum(e - s for s, e in sends))
        bind.append(sum(e - s for s, e in named("repro.prep.bind", lo, hi)))
    assert partition_ms.read(ctx) == pytest.approx(
        sum(cut) / 2 * 1e-6, rel=1e-9)
    assert transfer_ms.read(ctx) == pytest.approx(sum(send) / 2 * 1e-6,
                                                  rel=1e-9)
    assert bind_ms.read(ctx) == pytest.approx(sum(bind) / 2 * 1e-6,
                                              rel=1e-9)

    ops = raw_line(spans_raw, "/device:TPU:0", "XLA Ops")
    mods = [(s, e) for n, s, e in raw_line(spans_raw, "/device:TPU:0",
                                            "XLA Modules")
            if n.startswith("jit_step(")]
    held, busy, n_obs = 0.0, 0.0, 0
    for lo, hi in solves:
        # the i-th observation follows the solve's i-th step module
        steps = [m for m in mods if lo <= m[0] < hi]
        for (s, e), (_, step_end) in zip(named("repro.observe", lo, hi),
                                         steps):
            n_obs += 1
            a = max(s, step_end)
            if e <= a:
                continue
            held += e - a
            grid = np.zeros(int((e - a) / GRID_NS) + 1, bool)
            for _, x, y in ops:
                x, y = max(x, a), min(y, e)
                if y > x:
                    grid[int((x - a) / GRID_NS):
                         int(np.ceil((y - a) / GRID_NS))] = 1
            busy += min(grid.sum() * GRID_NS, e - a)
    got = observe_ms.read(ctx)
    assert n_obs == 4
    assert got["value"] == pytest.approx(held / 4 * 1e-6, rel=1e-9)
    assert got["note"]["device_busy_ms"] == pytest.approx(
        busy / 4 * 1e-6, rel=0.02, abs=2e-4)


def test_recorded_counters_equal_hand_counts(spans_raw):
    """The counters the spans carried on the chip (the profile's stats,
    which the reduction drops): the dense solve (2,048 x 512) and the
    sparse one (4,096 rows) on a 4 x 2 grid, each after a warm-up."""
    stats = []
    for plane in spans_raw.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                stats += [(e.name, {k: v for k, v in e.stats})
                          for e in line.events
                          if e.name.startswith("repro.")]
    sends = [s["bytes"] for n, s in stats if n == "repro.prep.transfer"]
    evals = [s["h2d_bytes"] for n, s in stats
             if n in ("repro.observe.primal", "repro.observe.dual")]
    (ell,) = [s for n, s in stats if n == "repro.prep.partition" and s]
    dense = 4 * 2048 * 512 + 4 * 2048          # X and y, float32
    n_p = 4096 // 4
    assert sends == [dense, 2 * 4 * 4 * 2 * n_p * ell["ell_k"]
                     + 2 * 4 * 4 * n_p]        # cells, labels and mask
    assert ell["useful_nnz"] + ell["padded_slots"] == (
        4 * 2 * n_p * ell["ell_k"])
    # the dense solve hands X and y to each evaluation; the sparse one
    # only y (its CSR went over in the warm-up)
    assert evals == [dense] * 4 + [4 * 4096] * 4
    assert [s["cache"] for n, s in stats if n == "repro.prep.bind"] == [
        "hit", "hit"]
