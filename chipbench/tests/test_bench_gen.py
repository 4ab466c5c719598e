"""The benchmark's own copies of the data generators: their properties
stand on their own, whatever the program's generators become."""
import numpy as np
import pytest

from chipbench.gen.dense import make_dense
from chipbench.gen.sparse import make_sparse


def test_dense_shape_labels_and_unit_variance():
    X, y = make_dense(300, 40, seed=3)
    assert X.shape == (300, 40) and X.dtype == np.float32
    assert y.shape == (300,) and set(np.unique(y)) == {-1.0, 1.0}
    np.testing.assert_allclose(X.std(axis=0), 1.0, rtol=1e-5)


@pytest.mark.parametrize("n,m,density", [(2000, 500, 0.02),
                                         (500, 3000, 0.004)])
def test_sparse_shape_density_and_labels(n, m, density):
    indptr, indices, data, y = make_sparse(n, m, density=density, seed=5)
    nnz = indptr[-1]
    assert indptr.shape == (n + 1,) and indices.shape == data.shape == (nnz,)
    assert np.all(np.diff(indptr) >= 1)              # every row has a label signal
    assert abs(nnz / (n * m) - density) < 0.15 * density + 1.0 / m
    assert indices.min() >= 0 and indices.max() < m
    for i in range(0, n, 97):                        # sorted, distinct columns
        row = indices[indptr[i]:indptr[i + 1]]
        assert np.all(np.diff(row) > 0)
    assert set(np.unique(y)) == {-1.0, 1.0}
    # unit-variance columns, zeros included
    X = np.zeros((n, m))
    X[np.repeat(np.arange(n), np.diff(indptr)), indices] = data
    std = X.std(axis=0)
    np.testing.assert_allclose(std[std > 0], 1.0, rtol=1e-4)


def test_same_seed_same_bytes_other_seed_other_bytes():
    a = make_dense(50, 20, seed=2 ** 31 + 11)
    b = make_dense(50, 20, seed=2 ** 31 + 11)
    c = make_dense(50, 20, seed=2 ** 31 + 12)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(a, b))
    assert a[0].tobytes() != c[0].tobytes()
    s1 = make_sparse(400, 300, density=0.03, seed=9)
    s2 = make_sparse(400, 300, density=0.03, seed=9)
    s3 = make_sparse(400, 300, density=0.03, seed=10)
    assert all(x.tobytes() == y.tobytes() for x, y in zip(s1, s2))
    assert s1[2].tobytes() != s3[2].tobytes()


def test_sparse_pattern_is_the_same_for_every_seed_in_another_order():
    a = make_sparse(600, 200, density=0.03, seed=2 ** 31 + 1)
    b = make_sparse(600, 200, density=0.03, seed=2 ** 31 + 2)

    def rows(indptr, indices):
        return sorted(tuple(indices[indptr[i]:indptr[i + 1]])
                      for i in range(len(indptr) - 1))

    assert rows(a[0], a[1]) == rows(b[0], b[1])
    assert a[1].tobytes() != b[1].tobytes()      # another order
