"""Required work per kernel call and per outer iteration, against counts
made by hand at tiny shapes."""
import pytest

from chipbench.cost import distinct_rows, least_seconds
from chipbench.cost.d3ca_step import outer_iteration
from chipbench.cost.sdca_dense import epoch as dense_epoch
from chipbench.cost.sdca_sparse import epoch as sparse_epoch

PEAKS = {"bf16_flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}


def test_distinct_rows():
    assert distinct_rows(1, 5) == 1.0
    assert distinct_rows(2, 1) == 1.0
    # two draws from two rows: 1 + 1/2 distinct rows expected
    assert distinct_rows(2, 2) == pytest.approx(1.5)
    assert distinct_rows(0, 3) == 0.0


def test_dense_epoch_by_hand():
    # 2 rows of 3 columns, 2 steps: 2 x (dot 6 + axpy 6) = 24 ops;
    # 1.5 distinct rows x 3 x 4 B + w in and out 2 x 3 x 4 B
    # + 4 per-row vectors x 2 x 4 B = 18 + 24 + 32
    ops, nbytes = dense_epoch(2, 3, steps=2)
    assert ops == 24.0
    assert nbytes == pytest.approx(18 + 24 + 32)


def test_sparse_epoch_counts_useful_nonzeros():
    # 4 rows, 10 columns, 6 useful nonzeros, 4 steps: a step meets 1.5
    # nonzeros on average -> 4 x 4 x 1.5 = 24 ops; distinct rows
    # 4 (1 - (3/4)^4) = 2.734375, each 1.5 x 8 B; w 2 x 10 x 4 B;
    # vectors 4 x 4 x 4 B
    ops, nbytes = sparse_epoch(4, 10, 4, nnz=6)
    assert ops == 24.0
    assert nbytes == pytest.approx(2.734375 * 12 + 80 + 64)
    # the padded width never enters: the same rows with more padding
    # cost the same
    assert sparse_epoch(4, 10, 4, nnz=6) == (ops, nbytes)


def test_outer_iteration_by_hand():
    # n 4, m 3, nnz 12, Q 1: local 48 + average 4 + map 24
    # + primal 24 + 12 + 6 + dual 24 + 8 + 6
    assert outer_iteration(4, 3, 12, 1) == 48 + 4 + 24 + 42 + 38
    # half an epoch per iteration halves the local steps' 48 alone
    assert outer_iteration(4, 3, 12, 1, epochs=0.5) == 24 + 4 + 24 + 42 + 38


def test_least_seconds_names_its_bound():
    assert least_seconds(100.0, 10.0, PEAKS) == (1.0, "memory")
    assert least_seconds(1000.0, 1.0, PEAKS) == (10.0, "compute")
