"""The mesh engines' padded-ELL layout on 8 forced host devices.

For every ``<case>.npz`` in the directory given as the one argument (a
CSR matrix with its labels, grid and ``k_multiple``), builds the blocks
with :func:`repro.core.prepare_shard_map_sparse` on a (data=P, model=Q)
mesh and writes ``<case>.out.npz``: the global ``(P*n_p, Q*k)`` ``cols``
and ``vals``, the ``(n_pad,)`` labels and mask, and the
``repro.prep.partition`` span's counters.

Executed as a subprocess by tests/test_ell_build.py (the device count
must be fixed before jax initializes).
"""
import os
import sys
from pathlib import Path

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import numpy as np

from repro.core import prepare_shard_map_sparse
from repro.data import CSRMatrix
from repro.launch.mesh import make_mesh
from repro.obs import Tracer


def main(directory):
    for path in sorted(Path(directory).glob("*.in.npz")):
        case = np.load(path)
        P, Q = (int(v) for v in case["grid"])
        csr = CSRMatrix(case["indptr"], case["indices"], case["data"],
                        tuple(int(v) for v in case["shape"]))
        tracer = Tracer()
        sdata = prepare_shard_map_sparse(
            make_mesh((P, Q), ("data", "model")), csr, case["y"],
            m_multiple=P * Q, k_multiple=int(case["k_multiple"]),
            tracer=tracer)
        (span,) = tracer.spans("repro.prep.partition")
        np.savez(path.with_name(path.name.replace(".in.", ".out.")),
                 cols=np.asarray(sdata.cols), vals=np.asarray(sdata.vals),
                 y=np.asarray(sdata.y), mask=np.asarray(sdata.mask),
                 **{key: np.asarray(value)
                    for key, value in span["args"].items()})


if __name__ == "__main__":
    main(sys.argv[1])
