"""Topology spec and hierarchical byte accounting (core/comm_model.py),
and the adaptive compression schedule.  Pure host-side math -- no
devices."""
import pytest

from repro.core.comm_model import (Topology, as_topology,
                                   hierarchical_accounting)
from repro.core.compress import (CompressionPolicy, CompressionSchedule,
                                 as_compression)


# ---------------------------------------------------------------------------
# topology specs
# ---------------------------------------------------------------------------

def test_topology_spec_roundtrip():
    t = Topology.from_spec("pods=4:int8:tree")
    assert (t.pods, t.codec, t.algo) == (4, "int8", "tree")
    assert Topology.from_spec(t.spec) == t
    # defaults fill in
    t2 = Topology.from_spec("pods=2")
    assert (t2.codec, t2.algo) == ("identity", "ring")
    assert t2.hierarchical() and not Topology(pods=1).hierarchical()


def test_topology_spec_errors():
    for bad in ("", "2", "pods=x", "pods=2:int8:tree:extra"):
        with pytest.raises(ValueError, match="spec|pod count"):
            Topology.from_spec(bad)
    with pytest.raises(ValueError, match="pods"):
        Topology(pods=0)
    with pytest.raises(ValueError, match="algo"):
        Topology(pods=2, algo="butterfly")


def test_as_topology():
    assert as_topology(None) is None
    assert as_topology("pods=2").pods == 2
    t = Topology(pods=3)
    assert as_topology(t) is t


# ---------------------------------------------------------------------------
# hierarchical byte accounting
# ---------------------------------------------------------------------------

def _acct(per_cell=4096, cells=8, op="psum", axis="data", name="g"):
    """Minimal wire_accounting dict with one collective."""
    return {"collectives": {
        name: {"payload_bytes_per_cell": per_cell, "cells": cells,
               "bytes_per_step": per_cell * cells, "op": op, "axis": axis}},
        "bytes_per_step": per_cell * cells,
        "uncompressed_bytes_per_step": per_cell * cells}


def test_hierarchical_accounting_tiers():
    # 8 data cells, 2 pods: intra carries the full per-cell payload per
    # cell; inter carries one codec payload per pod
    acct = _acct(per_cell=4096, cells=8, axis="data")
    topo = Topology(pods=2, codec="identity")
    out = hierarchical_accounting(acct, topo, {"data": 8, "model": 1})
    c = out["collectives"]["g"]
    assert c["intra_bytes_per_step"] == 4096 * 8
    assert c["inter_bytes_per_step"] == 4096 * 2
    assert out["bytes_per_step"] == 4096 * 10
    assert out["topology"] == topo.spec
    # int8 shrinks ONLY the inter-pod tier
    out8 = hierarchical_accounting(acct, Topology(pods=2, codec="int8"),
                                   {"data": 8, "model": 1})
    c8 = out8["collectives"]["g"]
    assert c8["intra_bytes_per_step"] == c["intra_bytes_per_step"]
    assert c8["inter_bytes_per_step"] < c["inter_bytes_per_step"] / 3
    # flat topology (or None) is a no-op passthrough
    assert hierarchical_accounting(acct, None, {}) is acct
    assert hierarchical_accounting(acct, Topology(pods=1), {}) is acct
    # collectives over OTHER axes are untouched
    other = _acct(per_cell=512, cells=8, axis="model")
    o = hierarchical_accounting(other, topo, {"data": 8, "model": 1})
    assert o["collectives"]["g"]["inter_bytes_per_step"] == 0.0
    assert o["collectives"]["g"]["bytes_per_step"] == 512 * 8


# ---------------------------------------------------------------------------
# adaptive compression schedule
# ---------------------------------------------------------------------------

def test_schedule_spec_parsing_and_roundtrip():
    s = CompressionSchedule.from_spec("adaptive")
    assert [p.spec for p in s.stages] == ["topk:0.25", "int8"]
    s2 = CompressionSchedule.from_spec(
        "adaptive:topk:0.1->int8->identity@slope=0.02@window=4")
    assert [p.spec for p in s2.stages] == ["topk:0.1", "int8", "identity"]
    assert (s2.slope_tol, s2.window) == (0.02, 4)
    # canonical spec round-trips
    assert CompressionSchedule.from_spec(s2.spec).spec == s2.spec


def test_schedule_spec_errors():
    with pytest.raises(ValueError, match="adaptive"):
        CompressionSchedule.from_spec("int8->identity")
    with pytest.raises(ValueError, match="unknown adaptive option"):
        CompressionSchedule.from_spec("adaptive@rate=2")
    with pytest.raises(ValueError, match="window"):
        CompressionSchedule(window=0)


def test_schedule_should_advance():
    s = CompressionSchedule(slope_tol=0.05, window=3)
    # too little history: never advance
    assert not s.should_advance([1.0, 0.9])
    # steep progress (a decade per iteration): keep the aggressive codec
    assert not s.should_advance([1.0, 0.1, 0.01, 1e-3])
    # flat progress: advance
    assert s.should_advance([0.5, 0.5, 0.5, 0.5])


def test_as_compression_dispatch():
    assert as_compression(None) is None
    assert isinstance(as_compression("int8"), CompressionPolicy)
    assert isinstance(as_compression("adaptive"), CompressionSchedule)
    sched = CompressionSchedule()
    assert as_compression(sched) is sched
