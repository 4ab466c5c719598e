"""The host's padded-ELL build against a plain loop, bit for bit.

``partition_sparse`` (the simulated grid's ``(P, Q, n_p, k)`` cells) and
``prepare_shard_map_sparse`` (the mesh engines' ``(P*n_p, Q*k)`` layout,
on 8 forced host devices in a subprocess) must give the cells the loop
below gives: each cell-row's entries in CSR order, ``k`` the largest
cell-row count rounded up to ``k_multiple``, padding (col 0, val 0.0).
The ``repro.prep.partition`` span says whether the CSR came ordered by
(row, feature block) (``ell_presorted`` 1) or had to be permuted (0).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import partition_sparse
from repro.data import CSRMatrix, csr_from_dense
from repro.obs import Tracer

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


def ceil_to(x, k):
    return -(-x // k) * k


def reference_ell(csr, y, P, Q, k_multiple):
    """The padded-ELL cells by a loop over the CSR entries: ``cols`` /
    ``vals`` ``(P, Q, n_p, k)``, labels and mask ``(P, n_p)``."""
    n, m = csr.shape
    n_p, m_q = ceil_to(n, P) // P, ceil_to(m, P * Q) // Q
    cells = {}
    for i in range(n):
        for s in range(csr.indptr[i], csr.indptr[i + 1]):
            c = int(csr.indices[s])
            q = min(c // m_q, Q - 1)
            cells.setdefault((i, q), []).append((c - q * m_q, csr.data[s]))
    k_max = max((len(entries) for entries in cells.values()), default=0)
    k = max(ceil_to(max(k_max, 1), k_multiple), k_multiple)
    cols = np.zeros((P, Q, n_p, k), np.int32)
    vals = np.zeros((P, Q, n_p, k), np.float32)
    for (i, q), entries in cells.items():
        p, r = divmod(i, n_p)
        for slot, (c, v) in enumerate(entries):
            cols[p, q, r, slot] = c
            vals[p, q, r, slot] = v
    y_blocks = np.zeros((P * n_p,), np.float32)
    y_blocks[:n] = y
    mask = np.zeros((P * n_p,), np.float32)
    mask[:n] = 1.0
    return cols, vals, y_blocks.reshape(P, n_p), mask.reshape(P, n_p)


def shuffle_rows(csr, rng):
    """The same matrix with each row's entries in a random order."""
    order = np.concatenate([
        lo + rng.permutation(hi - lo)
        for lo, hi in zip(csr.indptr[:-1], csr.indptr[1:])])
    return CSRMatrix(csr.indptr, csr.indices[order], csr.data[order],
                     csr.shape)


def dense_case(n, m, density, seed):
    rng = np.random.default_rng(seed)
    X = rng.uniform(-1.0, 1.0, (n, m)).astype(np.float32)
    X *= rng.random((n, m)) < density
    y = np.where(rng.random(n) < 0.5, -1.0, 1.0).astype(np.float32)
    return X, y


def make_case(name):
    """``(csr, y, P, Q, k_multiple, presorted)`` of case ``name``."""
    shuffled = name.endswith("_shuffled")
    base = name.removesuffix("_shuffled")
    P, Q, k_multiple = 4, 2, 8
    if base == "sorted":          # n = 37 is not a multiple of P = 4
        X, y = dense_case(37, 41, 0.2, seed=1)
    elif base == "empty_rows":
        X, y = dense_case(37, 41, 0.2, seed=2)
        X[[0, 5, 6, 36]] = 0.0
    elif base == "no_nnz":
        X, y = dense_case(13, 20, 0.0, seed=3)
    elif base == "one_cell":
        X, y = dense_case(30, 17, 0.3, seed=4)
        P, Q = 1, 1
    elif base == "k128":
        X, y = dense_case(37, 41, 0.2, seed=5)
        k_multiple = 128
    elif base == "heavy_row":     # one full row sets k for every cell
        X, y = dense_case(37, 41, 0.05, seed=6)
        X[9] = np.linspace(-1.0, 1.0, 41, dtype=np.float32)
    elif base == "grid_3x2":
        X, y = dense_case(50, 31, 0.25, seed=7)
        P, Q = 3, 2
    csr = csr_from_dense(X)
    if shuffled:
        csr = shuffle_rows(csr, np.random.default_rng(11))
    return csr, y, P, Q, k_multiple, 0 if shuffled else 1


CASES = ["sorted", "sorted_shuffled", "empty_rows", "empty_rows_shuffled",
         "no_nnz", "one_cell", "k128", "heavy_row", "heavy_row_shuffled",
         "grid_3x2_shuffled"]


def assert_bits(got, want):
    got = np.asarray(got)
    assert got.dtype == want.dtype and got.shape == want.shape
    np.testing.assert_array_equal(got.view(np.int32), want.view(np.int32))


@pytest.mark.parametrize("name", CASES)
def test_partition_sparse_matches_loop(name):
    csr, y, P, Q, k_multiple, presorted = make_case(name)
    tracer = Tracer()
    sp = partition_sparse(csr, y, P, Q, m_multiple=P * Q,
                          k_multiple=k_multiple, tracer=tracer)
    cols, vals, y_blocks, mask = reference_ell(csr, y, P, Q, k_multiple)
    for got, want in ((sp.cols, cols), (sp.vals, vals),
                      (sp.y_blocks, y_blocks), (sp.mask, mask)):
        assert_bits(got, want)
    (span,) = tracer.spans("repro.prep.partition")
    assert span["args"] == {"ell_k": cols.shape[-1], "useful_nnz": csr.nnz,
                            "padded_slots": cols.size - csr.nnz,
                            "ell_presorted": presorted}


@pytest.fixture(scope="module")
def mesh_layouts(tmp_path_factory):
    """Every case through ``prepare_shard_map_sparse`` on a forced
    8-device CPU mesh, in one subprocess; ``{name: outputs}``."""
    out = tmp_path_factory.mktemp("ell_mesh")
    for name in CASES:
        csr, y, P, Q, k_multiple, _ = make_case(name)
        np.savez(out / f"{name}.in.npz", indptr=csr.indptr,
                 indices=csr.indices, data=csr.data, shape=csr.shape, y=y,
                 grid=(P, Q), k_multiple=k_multiple)
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "helpers",
                                      "ell_mesh.py"), str(out)],
        env=ENV, timeout=300, capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    return {name: dict(np.load(out / f"{name}.out.npz")) for name in CASES}


@pytest.mark.shard_map
@pytest.mark.parametrize("name", CASES)
def test_mesh_layout_matches_loop(name, mesh_layouts):
    csr, y, P, Q, k_multiple, presorted = make_case(name)
    cols, vals, y_blocks, mask = reference_ell(csr, y, P, Q, k_multiple)
    n_p, k = cols.shape[2], cols.shape[3]
    got = mesh_layouts[name]
    # block (p, q) is the [p*n_p:(p+1)*n_p, q*k:(q+1)*k] tile
    assert_bits(got["cols"],
                cols.transpose(0, 2, 1, 3).reshape(P * n_p, Q * k))
    assert_bits(got["vals"],
                vals.transpose(0, 2, 1, 3).reshape(P * n_p, Q * k))
    assert_bits(got["y"], y_blocks.reshape(-1))
    assert_bits(got["mask"], mask.reshape(-1))
    assert int(got["ell_k"]) == k
    assert int(got["useful_nnz"]) == csr.nnz
    assert int(got["ell_presorted"]) == presorted
