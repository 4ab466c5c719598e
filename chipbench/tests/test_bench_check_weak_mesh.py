"""The check that decides ``correct``, on the four-chip weak-scaling cell
(``weak_1pct_2x2_mesh.cold``) at test size over four forced CPU devices,
in its own process since the device count is fixed when JAX starts: the
sound run passes under the configuration's own limits; the bfloat16
control and every planted fault, the exchanges left out among them,
fail.  And the cell as ``BENCHMARK.json`` lists it loads whole, without
the readers that a profile short of one chip's events would bias."""
import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "weak_1pct_2x2_mesh.cold"


def test_weak_mesh_cell_sound_control_and_faults():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "weak_mesh_cases.py")],
        capture_output=True, text=True, timeout=600, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    got = json.loads(proc.stdout.strip().splitlines()[-1])
    assert got.pop("sound") is True
    assert got.pop("control_bfloat16") is False
    assert got.pop("exchange_left_out") is False
    assert got and not any(got.values()), got


def test_weak_mesh_cell_loads_on_four_chips():
    from chipbench.harness import CHECKED, load_cell
    cell = load_cell(CELL)
    assert cell.chips == 4
    cfg = cell.config
    assert cfg["grid"][0] * cfg["grid"][1] == 4
    assert (cfg["engine"], cfg["block_format"]) == ("shard_map", "sparse")
    assert cfg["reduced"] == []
    assert all(k in cfg["check"] for k in CHECKED + ("sample",))
    assert cfg["check"]["unconverged"] == cfg["check"]["iters_over_K"] == 0
    names = {m["name"] for m in cell.per_layer}
    assert "collective_ms" in names
    # device 0's events end early in a four-chip profile: no reader that
    # averages the chips' device time over the window is listed
    assert not names & {"device_idle_share", "sdca_sparse_roofline",
                        "collective_exposed_ms"}
    assert [m["name"] for m in cell.end_to_end] == ["solve_s", "setup_s"]
