"""Each declared collective and D3CA's primal-dual map carry a named
scope (``repro.comm.<name>``, ``repro.d3ca.map``) into the op_name
metadata of the shard_map step, and the scopes change nothing else: the
same compiled program but for its metadata, bit-equal iterates (four
forced host devices, in a subprocess: the device count must be fixed
before jax initializes)."""
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))


@pytest.mark.shard_map
def test_shard_map_step_carries_its_scopes_and_nothing_else():
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "tests", "helpers",
                                      "comm_scopes.py")],
        env=ENV, timeout=300, capture_output=True, text=True, cwd=ROOT)
    assert r.returncode == 0, r.stdout + r.stderr
    assert r.stdout.strip().endswith("ok")
