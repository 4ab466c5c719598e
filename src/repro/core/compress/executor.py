"""CompressedComm: a Comm executor that compresses collective payloads.

Wraps any inner :class:`~repro.core.comm.Comm` (``SyncComm`` for the
grid/sync engines, ``StaleComm`` for the bounded-staleness async
engine), so compression composes with every communication policy: the
cell's contribution is encoded/decoded by the collective's codec
*before* the inner executor reduces it, and the async engine's
staleness rings then carry the reduction of dequantized values --
exactly the order a real bandwidth-saving all-reduce would impose
(quantize, put on the wire, reduce, delay consumption).

Error feedback: each stateful codec's residual enters through ``ef``
(one per-cell f32 buffer per compressed collective, sliced out of the
engine state pytree the same way the staleness rings are) and the
updated residuals come back out via :attr:`CompressedComm.ef_out`.

Wire accounting: every Comm executor records the exact payload bytes it
put on the wire per collective in ``.wire_bytes`` (the base class
records the uncompressed size; this class overrides it with the codec's
payload size).  :func:`wire_accounting` computes the same numbers
statically from a schedule + payload avals -- that is what the engines
attach to ``EngineProgram.comm_bytes`` and what surfaces in Solver
history and the metrics registry.
"""
from __future__ import annotations

import math
from typing import Dict, Optional

import jax.numpy as jnp

from ..comm import Comm, CommSchedule
from .codecs import IdentityCodec
from .policy import CompressionPolicy


class CompressedComm(Comm):
    """Compress each declared collective's payload per its policy codec,
    then delegate the actual reduction to the wrapped executor."""

    def __init__(self, inner: Comm, policy: CompressionPolicy,
                 ef: Optional[dict] = None):
        super().__init__(inner.schedule, inner.axis_map, inner.sizes)
        self.inner = inner
        self.policy = policy
        self.ef_in = dict(ef or {})
        #: updated error-feedback residuals, one per stateful collective
        self.ef_out: Dict[str, jnp.ndarray] = {}
        # staleness FIFO slots are produced by the inner executor; share
        # the dict object so the engine reads them off either comm
        self.bufs_out = inner.bufs_out

    # cell-facing index/size queries go to the inner executor (the
    # ShapeProbeComm override of axis_index must win under eval_shape)
    def axis_index(self, axis: str):
        return self.inner.axis_index(axis)

    def axis_size(self, axis: str) -> int:
        return self.inner.axis_size(axis)

    def _exec(self, point, value):
        codec = self.policy.codec_for(point.name)
        value = jnp.asarray(value)
        self.wire_bytes[point.name] = codec.payload_nbytes(
            value.shape, value.dtype)
        if codec.stateful:
            err = self.ef_in.get(point.name)
            if err is None:
                # build-time probing runs without buffers; a zero
                # residual has the right aval
                err = jnp.zeros(value.shape, jnp.float32)
            deq, new_err = codec.apply(value, err)
            self.ef_out[point.name] = new_err
            deq = deq.astype(value.dtype)
        else:
            deq, _ = codec.apply(value)
        return self.inner._exec(point, deq)

    def finalize(self):
        super().finalize()
        # run the inner executor's own contract checks (e.g. StaleComm's
        # buffer bookkeeping) against the points executed through us
        self.inner._executed = set(self._executed)
        self.inner.finalize()
        missing = (set(self.policy.stateful_names(self.schedule))
                   - set(self.ef_out))
        if missing:
            raise ValueError(
                f"error-feedback residuals never produced for compressed "
                f"collectives {sorted(missing)}")


# ---------------------------------------------------------------------------
# exact bytes-on-wire accounting
# ---------------------------------------------------------------------------

def wire_accounting(schedule: CommSchedule, payload_avals: dict,
                    sizes: dict,
                    policy: Optional[CompressionPolicy] = None) -> dict:
    """Exact per-step wire cost of one outer iteration.

    Every cell of the P x Q grid contributes one payload to each
    declared collective per step (psum/pmean/allgather alike), so a
    collective moves ``P * Q * payload_bytes`` per step; the codec
    decides the payload layout.  ``payload_avals`` maps collective name
    to the per-cell *input* aval (what the cell hands to ``comm``);
    ``sizes`` holds the logical grid extents.  Returns::

        {"collectives": {name: {op, axis, codec, payload_bytes_per_cell,
                                uncompressed_bytes_per_cell, cells,
                                bytes_per_step,
                                uncompressed_bytes_per_step}},
         "bytes_per_step": ...,            # sum over collectives
         "uncompressed_bytes_per_step": ...,
         "compression": <policy spec or None>}

    With no policy (or the identity codec) ``bytes_per_step`` equals
    ``uncompressed_bytes_per_step`` exactly -- the accounting invariant
    pinned in tests/test_compress.py.
    """
    identity = IdentityCodec()
    cells = int(sizes["data"]) * int(sizes["model"])
    per = {}
    total = 0
    total_raw = 0
    for point in schedule:
        aval = payload_avals[point.name]
        codec = policy.codec_for(point.name) if policy is not None \
            else identity
        raw = math.prod(aval.shape) * jnp.dtype(aval.dtype).itemsize
        comp = codec.payload_nbytes(aval.shape, aval.dtype)
        per[point.name] = {
            "op": point.op, "axis": point.axis, "codec": codec.name,
            # per-cell payload aval (what one cell hands to ``comm``)
            "payload_shape": tuple(int(d) for d in aval.shape),
            "payload_dtype": str(jnp.dtype(aval.dtype).name),
            "payload_bytes_per_cell": int(comp),
            "uncompressed_bytes_per_cell": int(raw),
            "cells": cells,
            "bytes_per_step": int(comp) * cells,
            "uncompressed_bytes_per_step": int(raw) * cells,
        }
        total += int(comp) * cells
        total_raw += int(raw) * cells
    return {"collectives": per,
            "bytes_per_step": total,
            "uncompressed_bytes_per_step": total_raw,
            "compression": policy.spec if policy is not None else None}
