"""Doubly distributed P x Q partitioning of the training matrix.

The paper stores block ``x_[p,q]`` (observations p, features q) on worker
(p, q) of a K = P*Q node cluster.  We provide:

  * ``DoublyPartitioned`` -- a padded, block-major view of (X, y) shaped
    ``(P, Q, n_p, m_q)`` used by the *simulated* grid execution (vmap over
    cells on one device) and, row/column-sharded, by the shard_map execution
    where each device holds exactly one ``(n_p, m_q)`` block in HBM.
  * helpers to scatter/gather the global primal/dual vectors to/from blocks.
  * the primal and dual objectives evaluated on the dense blocks, where
    they already are (``block_objective`` / ``block_dual_objective``).

Padding: rows are padded with x = 0 and mask = 0 so they contribute nothing
to objectives/gradients; columns are padded with zero features (harmless --
the corresponding w coordinates stay 0 under every update rule because the
data column is identically zero, and the regularizer only shrinks them).
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from ..obs.trace import PROFILER_TRACER, as_tracer
from .util import host_nbytes


def _ceil_to(x: int, k: int) -> int:
    return (x + k - 1) // k * k


@dataclasses.dataclass(frozen=True)
class DoublyPartitioned:
    """Block-major view of the training set."""

    x_blocks: jnp.ndarray   # (P, Q, n_p, m_q)
    y_blocks: jnp.ndarray   # (P, n_p)
    mask: jnp.ndarray       # (P, n_p)   1.0 = real row, 0.0 = padding
    n: int                  # true number of observations
    m: int                  # true number of features
    P: int
    Q: int

    @property
    def n_p(self) -> int:
        return self.x_blocks.shape[2]

    @property
    def m_q(self) -> int:
        return self.x_blocks.shape[3]

    # ---- global <-> block conversions -------------------------------------
    def w_to_blocks(self, w):
        """(m,) -> (Q, m_q), zero-padding the tail."""
        m_pad = self.Q * self.m_q
        wp = jnp.zeros((m_pad,), w.dtype).at[: self.m].set(w)
        return wp.reshape(self.Q, self.m_q)

    def w_from_blocks(self, w_blocks):
        """(Q, m_q) -> (m,)."""
        return w_blocks.reshape(-1)[: self.m]

    def alpha_to_blocks(self, alpha):
        n_pad = self.P * self.n_p
        ap = jnp.zeros((n_pad,), alpha.dtype).at[: self.n].set(alpha)
        return ap.reshape(self.P, self.n_p)

    def alpha_from_blocks(self, alpha_blocks):
        return alpha_blocks.reshape(-1)[: self.n]

    def dense(self):
        """Reassemble the (possibly padded) dense matrix (n, m) and labels."""
        Xp = jnp.transpose(self.x_blocks, (0, 2, 1, 3)).reshape(
            self.P * self.n_p, self.Q * self.m_q
        )
        return Xp[: self.n, : self.m], self.y_blocks.reshape(-1)[: self.n]

    # ---- objectives on the blocks -----------------------------------------
    def objective(self, loss, w, lam):
        """F(w) of the global ``(m,)`` iterate, on the device's blocks."""
        return block_objective(loss, self.x_blocks, self.y_blocks,
                               self.mask, w, lam=lam, n=self.n)

    def dual_objective(self, loss, alpha, lam):
        """D(alpha) of the global ``(n,)`` iterate, on the device's
        blocks."""
        return block_dual_objective(loss, self.x_blocks, self.y_blocks,
                                    self.mask, alpha, lam=lam, n=self.n)


# Both contractions are a multiply and a sum in float32: one pass over
# the blocks, exact to float32 rounding whatever the backend's default
# matmul precision.  The loss, lam and n are static, so every solve of
# one shape and config reuses one compiled evaluator and sends the
# device nothing.

@partial(jax.jit, static_argnames=("loss", "lam", "n"))
def block_objective(loss, x_blocks, y_blocks, mask, w, *, lam, n):
    """Primal objective F(w) of :meth:`Loss.objective` from the dense
    blocks ``(P, Q, n_p, m_q)``: z_p = sum_q X_pq w_q, the masked mean of
    ``loss.value`` over the ``n`` real rows, plus (lam/2) ||w||^2.  ``w``
    is the global ``(m,)`` iterate, zero-padded to the blocks here."""
    Q, m_q = x_blocks.shape[1], x_blocks.shape[3]
    w_b = jnp.pad(w, (0, Q * m_q - w.shape[0])).reshape(Q, m_q)
    z = jnp.sum(x_blocks * w_b[None, :, None, :], axis=(1, 3))
    return loss.objective_of_margins(z, y_blocks, w, lam, mask, n)


@partial(jax.jit, static_argnames=("loss", "lam", "n"))
def block_dual_objective(loss, x_blocks, y_blocks, mask, alpha, *, lam, n):
    """Dual objective D(alpha) of :meth:`Loss.dual_objective` from the
    dense blocks: v_q = sum_p X_pq^T alpha_p / (lam n), then
    -sum conj / n - (lam/2) ||v||^2.  ``alpha`` is the global ``(n,)``
    iterate, zero-padded to the ``(P, n_p)`` blocks here."""
    P, n_p = y_blocks.shape
    a_b = jnp.pad(alpha, (0, P * n_p - alpha.shape[0])).reshape(P, n_p)
    a_b = a_b * mask
    v = jnp.sum(x_blocks * a_b[:, None, :, None], axis=(0, 2)) / (lam * n)
    return loss.dual_objective_of_map(v, y_blocks, a_b, lam, mask, n)


def partition(X, y, P: int, Q: int, *,
              m_multiple: int | None = None,
              tracer=None) -> DoublyPartitioned:
    """Split (X, y) into the P x Q doubly distributed block grid.

    ``m_multiple`` pads the feature dimension to a multiple of that value
    instead of just Q.  The solver framework passes P*Q so that RADiSA's
    P sub-blocks divide every feature block and both engines see
    bit-identical blocks.

    The whole cut is a ``repro.prep.partition`` span in ``tracer``
    (default the profiler-only tracer); sending (X, y) to the device,
    where the blocks are cut, is a ``repro.prep.transfer`` span inside it.
    """
    tr = as_tracer(tracer, PROFILER_TRACER)
    if m_multiple is not None and m_multiple % Q:
        raise ValueError(f"m_multiple={m_multiple} not a multiple of Q={Q}")
    with tr.span("repro.prep.partition"):
        with tr.span("repro.prep.transfer", bytes=host_nbytes(X, y)):
            X = jnp.asarray(X)
            y = jnp.asarray(y)
        n, m = X.shape
        n_pad, m_pad = _ceil_to(n, P), _ceil_to(m, m_multiple or Q)
        n_p, m_q = n_pad // P, m_pad // Q

        Xp = jnp.zeros((n_pad, m_pad), X.dtype).at[:n, :m].set(X)
        yp = jnp.zeros((n_pad,), y.dtype).at[:n].set(y)
        mask = jnp.zeros((n_pad,), X.dtype).at[:n].set(1.0)

        x_blocks = Xp.reshape(P, n_p, Q, m_q).transpose(0, 2, 1, 3)
        y_blocks = yp.reshape(P, n_p)
        mask_blocks = mask.reshape(P, n_p)
        return DoublyPartitioned(x_blocks, y_blocks, mask_blocks, n, m, P, Q)


# ---------------------------------------------------------------------------
# sparse (padded ELL) cell format
# ---------------------------------------------------------------------------

def ell_gather(w, cols, vals):
    """Row inner products of an ELL block with a dense vector.

    ``w (m_q,)``, ``cols``/``vals`` ``(..., n_p, k)`` -> ``(..., n_p)``:
    each row's x_i . w as a gather of w at the row's column ids.
    Padding slots (col=0, val=0) read w[0] and contribute nothing.
    The single definition of the gather every sparse engine/cell uses.
    """
    return jnp.sum(vals * w[cols], axis=-1)


def ell_scatter_add(m_q: int, cols, vals, coef):
    """Column accumulation of an ELL cell: sum_i coef[i] * x_i -> (m_q,).

    ``cols``/``vals`` ``(n_p, k)``, ``coef (n_p,)``.  Scatter-ADD, so the
    duplicate index-0 padding slots (val=0) are inert.  The single
    definition of the scatter every sparse engine/cell uses (vmap it for
    block grids).
    """
    return jnp.zeros((m_q,), vals.dtype).at[cols].add(vals * coef[:, None])


def _ell_blocks(csr, y, P: int, Q: int, m_pad: int, k_multiple: int):
    """Host-side: bucket CSR rows into the P x Q grid as padded ELL cells.

    For every (p, q) cell each local row stores at most ``k`` entries as
    (block-local column id, value); ``k`` is the max per-cell-row nonzero
    count over the WHOLE grid, rounded up to ``k_multiple`` (lane
    alignment for the TPU kernels).  Padding slots use (col=0, val=0.0):
    every consumer either gathers (x0 reads are harmless) or scatter-ADDs
    (zero increments are inert), so the duplicate index-0 slots never
    change a result.

    Each cell-row keeps its entries in CSR order.  Sorted by their pair
    key ``row * Q + q``, the entries fill the ELL slots row-major: cell-row
    ``(row, q)`` takes its first ``count`` slots.  CSR rows come in order,
    so the key is already sorted when every row's columns ascend; only
    input that is not (a libsvm file with unordered columns) pays a stable
    permutation first.

    Returns numpy ``cols (n_pad, Q, k) int32`` and ``vals (n_pad, Q, k)
    f32``, row-major (``reshape(P, n_p, Q, k)`` gives cell ``(p, q)`` at
    ``[p, :, q]``), ``y_blocks (P, n_p)``, ``mask (P, n_p)``, and the
    span counters: ``ell_k``, the slots that hold an entry
    (``useful_nnz``), the padding slots (``padded_slots``), and
    ``ell_presorted`` (1 when the key came sorted, 0 when it was
    permuted).
    """
    n = csr.shape[0]
    n_pad = _ceil_to(n, P)
    n_p, m_q = n_pad // P, m_pad // Q

    key_t = np.int32 if n * Q < 2 ** 31 else np.int64
    indices, data = csr.indices, csr.data
    q_of = np.minimum(indices // m_q, Q - 1).astype(key_t)
    row = np.repeat(np.arange(n, dtype=key_t), np.diff(csr.indptr))
    pair = row * Q + q_of
    # per (row, q) nonzero count -> global k
    counts = np.bincount(pair, minlength=n * Q)
    k_max = int(counts.max()) if counts.size else 0
    k = max(_ceil_to(max(k_max, 1), k_multiple), k_multiple)

    presorted = bool(np.all(pair[1:] >= pair[:-1]))
    if not presorted:
        perm = np.argsort(pair, kind="stable")
        indices, data, q_of = indices[perm], data[perm], q_of[perm]
    slots = np.zeros((n_pad * Q, k), dtype=bool)
    slots[: n * Q] = np.arange(k) < counts[:, None]
    cols = np.zeros((n_pad * Q, k), dtype=np.int32)
    vals = np.zeros((n_pad * Q, k), dtype=np.float32)
    cols[slots] = indices - q_of * m_q
    vals[slots] = data

    yp = np.zeros((n_pad,), dtype=np.float32)
    yp[:n] = np.asarray(y, dtype=np.float32)
    maskp = np.zeros((n_pad,), dtype=np.float32)
    maskp[:n] = 1.0
    counters = {"ell_k": k, "useful_nnz": csr.nnz,
                "padded_slots": cols.size - csr.nnz,
                "ell_presorted": int(presorted)}
    return (cols.reshape(n_pad, Q, k), vals.reshape(n_pad, Q, k),
            yp.reshape(P, n_p), maskp.reshape(P, n_p), counters)


@dataclasses.dataclass(frozen=True)
class SparseDoublyPartitioned:
    """Block-major padded-ELL view of a sparse training set.

    The per-(p, q) cell is ``cols[p, q] (n_p, k) int32`` (block-local
    column ids in [0, m_q)) + ``vals[p, q] (n_p, k) f32``; peak block
    memory scales with the nonzero count (k ~= max cell-row nnz), not
    with m_q -- that is the whole point.
    """

    cols: jnp.ndarray       # (P, Q, n_p, k) int32, block-local columns
    vals: jnp.ndarray       # (P, Q, n_p, k) f32
    y_blocks: jnp.ndarray   # (P, n_p)
    mask: jnp.ndarray       # (P, n_p)   1.0 = real row, 0.0 = padding
    n: int                  # true number of observations
    m: int                  # true number of features
    m_q: int                # padded feature-block width
    P: int
    Q: int

    @property
    def n_p(self) -> int:
        return self.cols.shape[2]

    @property
    def k(self) -> int:
        return self.cols.shape[3]

    # ---- global <-> block conversions (same padding rule as dense) --------
    def w_to_blocks(self, w):
        m_pad = self.Q * self.m_q
        wp = jnp.zeros((m_pad,), w.dtype).at[: self.m].set(w)
        return wp.reshape(self.Q, self.m_q)

    def w_from_blocks(self, w_blocks):
        return w_blocks.reshape(-1)[: self.m]

    def alpha_to_blocks(self, alpha):
        n_pad = self.P * self.n_p
        ap = jnp.zeros((n_pad,), alpha.dtype).at[: self.n].set(alpha)
        return ap.reshape(self.P, self.n_p)

    def alpha_from_blocks(self, alpha_blocks):
        return alpha_blocks.reshape(-1)[: self.n]

    def dense(self):
        """Reassemble the dense (n, m) matrix and labels (tests only)."""
        Pn, Qn, n_p, k = self.cols.shape
        X = np.zeros((Pn * n_p, Qn * self.m_q), dtype=np.float32)
        cols = np.asarray(self.cols)
        vals = np.asarray(self.vals)
        p, q, r, s = np.meshgrid(np.arange(Pn), np.arange(Qn),
                                 np.arange(n_p), np.arange(k),
                                 indexing="ij")
        np.add.at(X, (p * n_p + r, q * self.m_q + cols), vals)
        y = np.asarray(self.y_blocks).reshape(-1)
        return X[: self.n, : self.m], y[: self.n]


def partition_sparse(X, y, P: int, Q: int, *, m_multiple: int | None = None,
                     k_multiple: int = 8,
                     tracer=None) -> SparseDoublyPartitioned:
    """Split (X, y) into the sparse P x Q padded-ELL block grid.

    ``X`` may be a :class:`~repro.data.sparse.CSRMatrix` (preferred --
    never densifies) or a dense array (converted row-wise).  The padding
    rule matches ``partition(..., m_multiple=...)`` exactly, so sparse
    and dense runs see the same logical blocks.

    The host's ELL build is a ``repro.prep.partition`` span carrying the
    ELL counters, sending the cells a ``repro.prep.transfer`` span, in
    ``tracer`` (default the profiler-only tracer).
    """
    from repro.data.sparse import CSRMatrix, csr_from_dense
    tr = as_tracer(tracer, PROFILER_TRACER)
    if m_multiple is not None and m_multiple % Q:
        raise ValueError(f"m_multiple={m_multiple} not a multiple of Q={Q}")
    with tr.span("repro.prep.partition") as span:
        if not isinstance(X, CSRMatrix):
            X = csr_from_dense(np.asarray(X))
        n, m = X.shape
        m_pad = _ceil_to(m, m_multiple or Q)
        cols, vals, y_blocks, mask, counters = _ell_blocks(
            X, y, P, Q, m_pad, k_multiple)
        span.set_metadata(**counters)
        n_p, k = y_blocks.shape[1], counters["ell_k"]
        # (n_pad, Q, k) -> (P, Q, n_p, k), copied here and not in the put
        cols = np.ascontiguousarray(
            cols.reshape(P, n_p, Q, k).transpose(0, 2, 1, 3))
        vals = np.ascontiguousarray(
            vals.reshape(P, n_p, Q, k).transpose(0, 2, 1, 3))
    with tr.span("repro.prep.transfer",
                 bytes=host_nbytes(cols, vals, y_blocks, mask)):
        return SparseDoublyPartitioned(
            cols=jnp.asarray(cols), vals=jnp.asarray(vals),
            y_blocks=jnp.asarray(y_blocks), mask=jnp.asarray(mask),
            n=n, m=m, m_q=m_pad // Q, P=P, Q=Q)


def numpy_partition_indices(n: int, P: int):
    """Host-side helper: index ranges of each observation partition."""
    n_pad = _ceil_to(n, P)
    n_p = n_pad // P
    return [(p * n_p, min((p + 1) * n_p, n)) for p in range(P)]
