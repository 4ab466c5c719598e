"""``collective_ms`` on hand-built device events of two chips: the ops
under a ``repro.comm.`` scope (read from their op_name metadata) inside
the traced window, over each chip's own step modules, mean over the
chips; the note's time per scope and of every all-reduce by its HLO
kind; a program without the scopes, or nothing to read, gives None.  And
the metadata read from a program this process compiled."""
import types

import pytest

from chipbench import trace_reduce as tr
from chipbench.metrics import collective_ms

MODULE = "jit_step_fn(123)"
OP_NAMES = {
    ("jit_step_fn", "psum.12"): "jit(step_fn)/shard_map/repro.comm.dalpha/psum",
    ("jit_step_fn", "psum.13"): ("jit(step_fn)/shard_map/repro.d3ca.map/"
                                 "repro.comm.w_contrib/psum"),
    ("jit_step_fn", "fusion.4"): ("jit(step_fn)/shard_map/repro.d3ca.map/"
                                  "scatter-add"),
    ("jit_step_fn", "sdca_sparse.1"): ("jit(step_fn)/shard_map/sdca_sparse/"
                                       "pallas_call"),
}


def op(name, kind, start, end):
    return tr.Event(f"%{name} = f32[8]{{0}} {kind}(f32[8]{{0}} %p)",
                    float(start), float(end))


def step_ops(t0, s):
    """One outer step from ``t0``: a kernel, the dalpha all-reduce, the
    map's scatter-add and the w_contrib all-reduce; durations times
    ``s``."""
    return [op("sdca_sparse.1", "custom-call", t0 + 10, t0 + 10 + 300 * s),
            op("psum.12", "all-reduce", t0 + 410, t0 + 410 + 20 * s),
            op("fusion.4", "fusion", t0 + 510, t0 + 510 + 30 * s),
            op("psum.13", "all-reduce", t0 + 610, t0 + 610 + 10 * s)]


def chip(index, scale, steps=2):
    """One chip's profile: a step before the window, ``steps`` steps in
    it, an objective's all-reduce outside any step module, and a step
    after the window; a chip whose events end early holds fewer steps
    and no objective."""
    s = scale
    ops = [op("psum.12", "all-reduce", 50, 50 + 7 * s)]         # before
    modules = [tr.Event(MODULE, 40.0, 80.0 + 7 * s)]
    for k in range(steps):
        ops += step_ops(90 + 1000 * k, s)
        modules.append(tr.Event(MODULE, 90.0 + 1000 * k, 1000.0 + 1000 * k))
    if steps == 2:
        ops.append(op("all-reduce.7", "all-reduce", 2500, 2500 + 5 * s))
        ops += step_ops(3090, s)                                 # after
        modules.append(tr.Event(MODULE, 3090.0, 4000.0))
    return tr.Device(index, ops, modules)


def ctx_of(devices):
    trace = tr.Trace(devices, [], [])
    return types.SimpleNamespace(trace=trace, window=(90.0, 3000.0),
                                 chips=len(devices))


@pytest.fixture
def metadata(monkeypatch):
    monkeypatch.setattr(collective_ms, "live_op_names",
                        lambda modules: {k: v for k, v in OP_NAMES.items()
                                         if k[0] in modules})


def test_scoped_collectives_per_own_step_mean_over_chips(metadata):
    # chip 1's events end after its first step in the window
    got = collective_ms.read(ctx_of([chip(0, 1.0), chip(1, 2.0, steps=1)]))
    # per step: chip 0 20 + 10 ns over its 2 steps, chip 1 40 + 20 over 1
    assert got["value"] == pytest.approx((30 + 60) / 2 * 1e-6)
    note = got["note"]
    assert note["repro.comm.dalpha"] == pytest.approx((20 + 40) / 2 * 1e-6)
    assert note["repro.comm.w_contrib"] == pytest.approx((10 + 20) / 2
                                                         * 1e-6)
    # the map: its scatter-add and its psum
    assert note["repro.d3ca.map"] == pytest.approx((40 + 80) / 2 * 1e-6)
    # every all-reduce of the window, the objective's outside the steps
    # included: chip 0 (60 + 5) / 2, chip 1 60 / 1
    assert note["all_collectives"] == pytest.approx((32.5 + 60) / 2 * 1e-6)
    assert set(note) == {"all_collectives", "repro.comm.dalpha",
                         "repro.comm.w_contrib", "repro.d3ca.map"}


def test_a_program_without_scopes_reads_none(monkeypatch):
    monkeypatch.setattr(collective_ms, "live_op_names", lambda modules: {
        ("jit_step_fn", "psum.12"): "jit(step_fn)/shard_map/psum"})
    assert collective_ms.read(ctx_of([chip(0, 1.0), chip(1, 1.0)])) is None


def test_nothing_to_read_reads_none(metadata):
    dev = chip(0, 1.0)
    dev.ops[:] = [e for e in dev.ops if e.kind != "all-reduce"]
    assert collective_ms.read(ctx_of([dev])) is None
    no_steps = chip(0, 1.0)
    no_steps.modules[:] = []
    assert collective_ms.read(ctx_of([no_steps])) is None
    assert collective_ms.read(ctx_of([])) is None


def test_live_op_names_read_a_compiled_program():
    import jax
    import jax.numpy as jnp

    def scoped(x):
        with jax.named_scope("repro.comm.probe"):
            return jnp.sin(x) * 2.0

    jitted = jax.jit(scoped)
    jitted(jnp.ones((8,))).block_until_ready()
    names = collective_ms.live_op_names({"jit_scoped"})
    assert names and all(module == "jit_scoped" for module, _ in names)
    assert any(collective_ms.scopes(v) == ("repro.comm.probe",)
               for v in names.values())
    assert collective_ms.live_op_names({"jit_no_such_program"}) == {}
