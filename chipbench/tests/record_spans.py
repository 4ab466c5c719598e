#!/usr/bin/env python3
"""Record the small one-chip trace that ``test_bench_program_spans.py``
reads: two D3CA solves of two outer steps each (a dense and a sparse
4x2 grid, Pallas kernels) through ``Solver.solve``, with the program's
own ``repro.*`` spans, inside the benchmark's ``chipbench.window`` and
``chipbench.solve`` spans.  Run from the root of a checkout, on a TPU:

    python3 chipbench/tests/record_spans.py chiprun_out/spans.xplane.pb.gz

Both solves run once before the trace, so it holds no compilation.  It
prints a few device events of the Pallas kernels with their stats, to
show where each kernel's name appears.
"""
from __future__ import annotations

import gzip
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(out: str) -> int:
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax
    from jax.profiler import ProfileData, ProfileOptions, TraceAnnotation
    from repro.core import D3CAConfig, get_solver
    from repro.data import make_svm_data
    from repro.data.sparse import make_sparse_svm_csr
    if jax.devices()[0].platform != "tpu":
        print("record_spans: no TPU; nothing recorded", file=sys.stderr)
        return 2
    cfg = D3CAConfig(lam=1e-2, outer_iters=2)
    cases = [("dense",) + make_svm_data(2048, 512, seed=0),
             ("sparse",) + make_sparse_svm_csr(4096, 2048, density=0.01,
                                               seed=0)]
    # the program cache keeps each solve's jitted step, as the benchmark's
    # solver does, so the traced solves compile nothing
    solvers = {fmt: get_solver("d3ca")(engine="simulated",
                                       local_backend="pallas",
                                       block_format=fmt, program_cache=True)
               for fmt, _, _ in cases}

    def solve(fmt, X, y):
        return solvers[fmt].solve("hinge", X, y, P=4, Q=2, cfg=cfg)

    for case in cases:
        solve(*case)
    tmp = tempfile.mkdtemp()
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with TraceAnnotation("chipbench.window"):
        for i, case in enumerate(cases):
            with TraceAnnotation("chipbench.solve", index=i):
                solve(*case)
    jax.profiler.stop_trace()
    (path,) = Path(tmp).rglob("*.xplane.pb")
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    with open(path, "rb") as src, gzip.open(out, "wb") as dst:
        shutil.copyfileobj(src, dst)
    names = set()
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if "tpu_custom_call" in e.name:
                    stats = {k: str(v)[:160] for k, v in e.stats}
                    names.add(f"{plane.name} {line.name}: {e.name[:160]} "
                              f"{stats}")
    for name in sorted(names)[:8]:
        print("kernel op:", name)
    print(f"record_spans: {Path(out).stat().st_size} bytes -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
