"""A run that finds no TPU fails before any work and prints no result;
so does a run in a checkout that holds only the benchmark."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
ARGS = ["--workload", "synth_realsim_4x2.cold", "--seed", "2147483700",
        "--seconds", "1", "--trace", "0"]


def no_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if "metrics" in json.loads(line):
                return False
        except ValueError:
            continue
    return True


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=300, env=env)
    assert proc.returncode != 0
    assert no_result(proc.stdout), proc.stdout
    assert "no TPU" in proc.stderr


def test_benchmark_alone_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    proc = subprocess.run([sys.executable, "chipbench/run.py", *ARGS],
                          cwd=tmp_path, capture_output=True, text=True,
                          timeout=300, env=env)
    assert proc.returncode != 0
    assert no_result(proc.stdout), proc.stdout


def test_unknown_workload_exits_nonzero():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chipbench/run.py",
                           "--workload", "no_such.cell", *ARGS[2:]],
                          cwd=ROOT, capture_output=True, text=True,
                          timeout=300, env=env)
    assert proc.returncode != 0
    assert no_result(proc.stdout)


def test_peaks_table_names_its_source_and_refuses_unknown_kinds():
    from chipbench.harness import peaks_for
    with open(os.path.join(ROOT, "chipbench", "peaks.json")) as f:
        assert "cloud.google.com" in json.load(f)["source"]
    entry = peaks_for("TPU v5 lite")
    assert entry["bf16_flops_per_s"] == 197e12
    assert entry["hbm_bytes_per_s"] == 819e9
    assert entry["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks_for("TPU v4")
