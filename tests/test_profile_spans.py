"""The solver's spans in a JAX profile, on the CPU: a tiny D3CA solve,
dense and sparse, under ``jax.profiler``; the span tree, its counters
against hand counts from the shapes, and what a solve without a tracer
does (no sync of its own, no events, the same answer)."""
import tempfile
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import D3CAConfig, get_solver
from repro.data import make_svm_data
from repro.data.sparse import make_sparse_svm_csr
from repro.obs import PROFILER_TRACER, Tracer

P, Q, N, M = 2, 2, 60, 24
CFG = D3CAConfig(lam=1e-1, outer_iters=3, local_steps=8)

#: span -> the span it sits in, as the solver opens them
PARENT = {"repro.prep": "repro.solve", "repro.iter": "repro.solve",
          "repro.result": "repro.solve",
          "repro.prep.partition": "repro.prep",
          "repro.prep.bind": "repro.prep",
          "repro.step": "repro.iter", "repro.observe": "repro.iter",
          "repro.observe.primal": "repro.observe",
          "repro.observe.dual": "repro.observe"}


def problem(fmt):
    if fmt == "dense":
        return make_svm_data(N, M, seed=0)
    return make_sparse_svm_csr(N, M, density=0.2, seed=0)


def profiled(fn):
    """Run ``fn`` under the profiler; returns its result and the program's
    spans on the host, each ``(name, start, end, stats)``."""
    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as out:
        jax.profiler.start_trace(out)
        try:
            result = fn()
        finally:
            jax.profiler.stop_trace()
        (path,) = Path(out).rglob("*.xplane.pb")
        planes = ProfileData.from_file(str(path)).planes
        spans = []
        for plane in planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("repro."):
                        stats = {key: value for key, value in e.stats}
                        spans.append((e.name, e.start_ns,
                                      e.start_ns + e.duration_ns, stats))
    return result, sorted(spans, key=lambda s: (s[1], -s[2]))


def parent_of(span, spans):
    """The innermost other span that holds ``span``."""
    holders = [s for s in spans if s is not span and s[1] <= span[1]
               and span[2] <= s[2]]
    return max(holders, key=lambda s: s[1])[0] if holders else None


def named(spans, name):
    return [s for s in spans if s[0] == name]


@pytest.fixture(scope="module", params=["dense", "sparse"])
def traced(request):
    fmt = request.param
    X, y = problem(fmt)
    solver = get_solver("d3ca")(engine="simulated", block_format=fmt)
    res, spans = profiled(lambda: solver.solve("hinge", X, y, P=P, Q=Q,
                                               cfg=CFG))
    return fmt, X, y, res, spans


@pytest.mark.obs
def test_span_tree_nests_as_the_solver_opens_it(traced):
    fmt, _, _, res, spans = traced
    (solve,) = named(spans, "repro.solve")
    assert solve[3] == {"solver": "d3ca", "engine": "simulated"}
    assert parent_of(solve, spans) is None
    for span in spans:
        if span[0] in PARENT:
            assert parent_of(span, spans) == PARENT[span[0]], span
    # the dense path sends X inside its cut; the sparse path after it
    for send in named(spans, "repro.prep.transfer"):
        assert parent_of(send, spans) == ("repro.prep.partition"
                                          if fmt == "dense" else "repro.prep")
    for name in ("repro.iter", "repro.step", "repro.observe",
                 "repro.observe.primal", "repro.observe.dual"):
        assert [s[3]["iter"] for s in named(spans, name)] == list(
            range(1, res.iters + 1))
    assert res.iters == CFG.outer_iters
    assert named(spans, "repro.prep.bind")[0][3] == {"cache": "off"}
    assert not {s[0] for s in spans} - set(PARENT) - {
        "repro.solve", "repro.prep.transfer"}


@pytest.mark.obs
def test_counters_equal_hand_counts(traced):
    fmt, X, y, _, spans = traced
    sent = sum(s[3]["bytes"] for s in named(spans, "repro.prep.transfer"))
    primal = [s[3]["h2d_bytes"] for s in named(spans, "repro.observe.primal")]
    dual = [s[3]["h2d_bytes"] for s in named(spans, "repro.observe.dual")]
    operands = {s[3]["operands"] for s in spans
                if s[0] in ("repro.observe.primal", "repro.observe.dual")}
    n_p = -(-N // P)
    if fmt == "dense":
        # X (N, M) and y (N,) in float32, sent once to be cut; each
        # evaluation reads the blocks already on the device, and sends
        # nothing
        assert sent == 4 * N * M + 4 * N
        assert primal == dual == [0] * CFG.outer_iters
        assert operands == {"blocks"}
        assert "ell_k" not in named(spans, "repro.prep.partition")[0][3]
        return
    assert operands == {"host"}
    # ELL: k is the most nonzeros of a row inside one feature block,
    # rounded up to 8; every slot of the P x Q x n_p x k grid that holds
    # no entry is padding; the generator's rows have ascending columns,
    # so the build takes them as they come
    m_q = -(-M // (P * Q)) * (P * Q) // Q
    rows = np.repeat(np.arange(N), np.diff(X.indptr))
    per_row_block = np.zeros((N, Q), int)
    np.add.at(per_row_block, (rows, X.indices // m_q), 1)
    k = -(-per_row_block.max() // 8) * 8
    (cut,) = named(spans, "repro.prep.partition")
    assert cut[3] == {"ell_k": k, "useful_nnz": X.nnz,
                      "padded_slots": P * Q * n_p * k - X.nnz,
                      "ell_presorted": 1}
    # cols (int32) and vals (float32) of every cell, labels and mask
    assert sent == 2 * 4 * P * Q * n_p * k + 2 * 4 * P * n_p
    # the CSR triplet (float32 values, int32 columns, int64 rows) goes
    # over once, with the first evaluation; the labels with every one
    assert primal == [16 * X.nnz + 4 * N] + [4 * N] * (
        CFG.outer_iters - 1)
    assert dual == [4 * N] * CFG.outer_iters


@pytest.mark.obs
@pytest.mark.parametrize("fmt", ["dense", "sparse"])
def test_profiler_on_and_off_give_the_same_answer(fmt):
    X, y = problem(fmt)
    solver = get_solver("d3ca")(engine="simulated", block_format=fmt)
    off = solver.solve("hinge", X, y, P=P, Q=Q, cfg=CFG)
    on, _ = profiled(lambda: solver.solve("hinge", X, y, P=P, Q=Q, cfg=CFG))
    assert np.array_equal(np.asarray(off.w), np.asarray(on.w))
    assert np.array_equal(np.asarray(off.alpha), np.asarray(on.alpha))
    assert off.history == [{**h, "time_s": o["time_s"]}
                           for h, o in zip(on.history, off.history)]


def count_syncs(monkeypatch):
    calls = []
    real = jax.block_until_ready
    monkeypatch.setattr(jax, "block_until_ready",
                        lambda x: calls.append(1) or real(x))
    return calls


@pytest.mark.obs
def test_solve_without_tracer_adds_no_sync_and_keeps_no_events(monkeypatch):
    X, y = problem("dense")
    solver = get_solver("d3ca")(engine="simulated")
    calls = count_syncs(monkeypatch)
    res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=CFG)
    assert res.iters == CFG.outer_iters
    assert calls == []
    assert PROFILER_TRACER.events == [] and not PROFILER_TRACER.enabled
    assert not {"step_s", "host_s"} & set(res.history[0])


@pytest.mark.obs
def test_tracer_blocks_each_step_without_calibrating(monkeypatch):
    X, y = problem("dense")
    solver = get_solver("d3ca")(engine="simulated")
    calls = count_syncs(monkeypatch)
    tr = Tracer()
    res = solver.solve("hinge", X, y, P=P, Q=Q, cfg=CFG, tracer=tr)
    assert len(calls) == res.iters
    assert {"step_s", "host_s"} <= set(res.history[0])
    assert not {"local_s", "comm_s"} & set(res.history[0])
    assert len(tr.spans("repro.step")) == res.iters
