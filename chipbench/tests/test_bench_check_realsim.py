"""The check that decides ``correct``, on the sparse real-sim cell at test
size: sound runs pass; the bfloat16 control and each planted fault fail."""
import pytest

from bench_cases import FAULTS, run

CELL = "synth_realsim_4x2.cold"


def test_sound_run_is_correct():
    out = run(CELL)
    assert out["correct"], out["numbers"]
    assert out["metrics"]["solve_s"]["value"] > 0


@pytest.mark.parametrize("dtype,correct", [("float32", True),
                                           ("bfloat16", False)])
def test_reference_in_program_place(dtype, correct):
    out = run(CELL, control=dtype)
    assert out["correct"] is correct, out["numbers"]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(fault):
    out = run(CELL, fault=fault)
    assert not out["correct"], out["numbers"]
