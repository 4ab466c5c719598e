"""The check that decides ``correct``, on the weak-scaling shard_map cell
at test size over four forced CPU devices (its own process, since the
device count is fixed when JAX starts): the sound run passes; the
bfloat16 control and every planted fault, the collectives left out among
them, fail."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))


def test_mesh_cell_sound_control_and_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, os.path.join(HERE, "mesh_cases.py")],
                          capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got.pop("sound") is True
    assert got.pop("control_bfloat16") is False
    assert got and not any(got.values()), got
