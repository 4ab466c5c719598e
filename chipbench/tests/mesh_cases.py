"""The weak-scaling cell's check at test size on four forced CPU devices,
in a process of its own: ``python mesh_cases.py`` prints one JSON line,
``{case: correct}``, for the sound run, the bfloat16 control and every
fault a mesh cell can have."""
import json
import os
import sys

os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_PLATFORMS"] = "cpu"
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.abspath(os.path.join(HERE, "..", ".."))
sys.path[:0] = [HERE, ROOT, os.path.join(ROOT, "src")]

from bench_cases import FAULTS, run  # noqa: E402

CELL = "weak_1pct_2x2.cold"


def main():
    out = {"sound": run(CELL)["correct"],
           "control_bfloat16": run(CELL, control="bfloat16")["correct"]}
    for fault in FAULTS:
        out[fault] = run(CELL, fault=fault)["correct"]
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
