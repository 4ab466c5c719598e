"""The reduction from a profiler trace to what the per-layer metrics
read: interval arithmetic on hand-made events, and the metrics on a
hand-made two-device trace whose answers are counted by hand."""
import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Device, Event, Trace


KERNEL = ('%closed_call.9 = (f32[2,128]{1,0}) custom-call(s32[128]{0} %a), '
          'custom_call_target="tpu_custom_call"')
PSUM = "%psum.3 = f32[40000]{0} all-reduce(f32[40000]{0} %b), to_apply=%sum"


def ev(name, start, end, **stats):
    return Event(name, float(start), float(end), stats)


def test_union_gaps_and_uncovered():
    spans = [(0, 10), (5, 20), (30, 40), (40, 45), (50, 50)]
    assert tr.union(spans) == [(0, 20), (30, 45)]
    assert tr.length(spans) == 35
    assert tr.gaps(spans, -5, 60) == [(-5, 0), (20, 30), (45, 60)]
    assert tr.clip(spans, 8, 35) == [(8, 10), (8, 20), (30, 35)]
    # collective 0-30 against compute 5-10 and 20-25: 20 of 30 bare
    assert tr.uncovered([(0, 30)], [(5, 10), (20, 25)]) == 20


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_bisected_gaps_and_within_agree_with_a_plain_scan(seed):
    import random
    rng = random.Random(seed)
    spans = []
    for _ in range(300):
        s = rng.uniform(0, 1000)
        spans.append((s, s + rng.uniform(0, 8)))
    cover = tr.union(spans)
    events = sorted((ev("op", s, e) for s, e in spans),
                    key=lambda x: x.start)
    for _ in range(200):
        lo = rng.uniform(-10, 1010)
        hi = lo + rng.uniform(0, 50)
        # a plain scan: walk every covered interval
        plain, cursor = [], lo
        for s, e in cover:
            s, e = max(s, lo), min(e, hi)
            if e <= s:
                continue
            if s > cursor:
                plain.append((cursor, s))
            cursor = max(cursor, e)
        if hi > cursor:
            plain.append((cursor, hi))
        assert tr.bare(cover, lo, hi) == plain
        assert tr.gaps(spans, lo, hi) == plain
        assert tr.within(events, lo, hi) == [x for x in events
                                             if lo <= x.start < hi]


def two_device_trace():
    """Window 0-1000 ns.  Two solves (spans 0-450, 500-1000), each of two
    outer steps (module ``jit_step``); the kernel runs inside each step,
    an all-reduce at each step's end, host work between steps."""
    def device(i, shift):
        ops, mods = [], []
        for s0 in (100, 300, 600, 800):
            s = s0 + shift
            mods.append(ev(f"jit_step({i})", s, s + 100))
            ops.append(ev(KERNEL, s, s + 80))
            ops.append(ev(PSUM, s + 70, s + 100))
        ops.append(ev("%fusion.2 = f32[8]{0} fusion(%a)", 460, 470))
        # a loop holding the first step's kernel counts as no compute
        ops.append(ev("%while.1 = (s32[]) while((s32[]) %t)", 100 + shift,
                      190 + shift))
        return Device(i, sorted(ops, key=lambda e: e.start), mods)

    annotations = [ev("chipbench.window", 0, 1000),
                   ev("chipbench.solve", 0, 450, index=0),
                   ev("chipbench.solve", 500, 1000, index=1)]
    host = [ev("PjitFunction(step)", 90, 95), ev("observe-ish", 210, 290)]
    return Trace([device(0, 0), device(1, 10)], annotations, host)


class Rec:
    def __init__(self, iters):
        self.iters = iters


class Ctx:
    def __init__(self, trace):
        self.trace = trace
        self.window = (0.0, 1000.0)
        spans = trace.annotation("chipbench.solve")
        self.solves = [(spans[0], Rec(2)), (spans[1], Rec(2))]
        self.chips = 2
        self.iters = 4
        self.window_s = 1e-6


def test_busy_union_over_devices():
    t = two_device_trace()
    # per device: 4 x (80 kernel + 30 all-reduce - 10 overlap) + 10 = 410
    # (the loop lies inside the first step's busy time)
    for d in t.devices:
        assert tr.length(tr.spans(d.ops)) == 410
    from chipbench.metrics import device_idle_share
    assert device_idle_share.read(Ctx(t)) == pytest.approx(59.0)


def test_gaps_between_steps_and_prep():
    from chipbench.metrics import host_gap_ms, prep_ms
    t = two_device_trace()
    # inside each solve one gap between its two steps: 200-300 and
    # 700-800 (100 ns each) on each device; 4 steps per device
    assert host_gap_ms.read(Ctx(t)) == pytest.approx(
        (2 * 100 * 2) / (4 * 2) * 1e-6)
    # first step of solve 0 at 100 (device 0), of solve 1 at 600
    assert prep_ms.read(Ctx(t)) == pytest.approx((100 + 100) / 2 * 1e-6)


def test_collective_time_not_covered_by_compute():
    from chipbench.metrics import collective_exposed_ms
    t = two_device_trace()
    # each all-reduce 30 ns, 10 of them under the kernel: 20 bare, four
    # per device, over 4 outer steps
    assert collective_exposed_ms.read(Ctx(t)) == pytest.approx(20e-6)


def test_host_doing_names_the_longest_cover():
    t = two_device_trace()
    assert tr.host_doing(t, 200, 300) == "observe-ish"
    assert tr.host_doing(t, 400, 450) is None


def test_op_names_and_kinds_from_hlo_text():
    k, p = ev(KERNEL, 0, 1), ev(PSUM, 0, 1)
    assert k.op == "closed_call.9 (tpu_custom_call)"
    assert k.kind == "custom-call"
    assert p.op == "psum.3" and p.kind == "all-reduce"
    assert ev("%while.1 = (s32[], f32[4]{0:T(4)}) while((s32[]) %t)",
              0, 1).kind == "while"
    assert ev("chipbench.solve", 0, 1).kind == ""
    from chipbench.metrics import collective_exposed_ms, sdca_sparse_roofline
    assert tr.matches(p, collective_exposed_ms.COLLECTIVES)
    assert not tr.matches(k, collective_exposed_ms.COLLECTIVES)
    # an op that reads a collective's result is no collective
    assert not tr.matches(ev("%fusion.4 = f32[8]{0} fusion(%all-reduce.2)",
                             0, 1), collective_exposed_ms.COLLECTIVES)
    assert tr.matches(k, sdca_sparse_roofline.KERNELS)
