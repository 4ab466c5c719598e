"""The four-chip weak-scaling cell's check at test size on four forced
CPU devices, in a process of its own: ``python weak_mesh_cases.py``
prints one JSON line, ``{case: correct}``, for the sound run, the
bfloat16 control and every planted fault, each under the limits
of ``configs/weak_1pct_2x2_mesh.json`` itself."""
import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]

import bench_cases  # noqa: E402

CELL = "weak_1pct_2x2_mesh.cold"
#: the cell at test size: 800 x 400 at 5% on the 2x2 mesh, lam 1.0 as
#: configured; the float32 program stops at K = 4 on gap target 0.2
bench_cases.TINY[CELL] = {"data": {"n": 800, "m": 400, "density": 0.05},
                          "lam": 1.0, "gap_target": 0.2, "K": 4}


def main():
    out = {"sound": bench_cases.run(CELL)["correct"],
           "control_bfloat16": bench_cases.run(
               CELL, control="bfloat16")["correct"]}
    for fault in bench_cases.FAULTS:
        out[fault] = bench_cases.run(CELL, fault=fault)["correct"]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
