"""Traffic is found by name: a mix's parameters drive the general closed
loop (a schedule of per-solve requests: warm starts, other lambdas), and a
mix that needs code brings ``traffic/<traffic>.py``."""
import dataclasses
import json
import time

import pytest

from bench_cases import run, tiny_cell


def test_schedule_in_mix_data_drives_warm_starts_and_lambdas(monkeypatch):
    import bench_cases
    cell = tiny_cell("part1_4x2.cold")
    schedule = [{}, {"start": "warm", "K": 1},
                {"lam": 0.02, "gap_target": 0.45, "K": 4}]
    cell = dataclasses.replace(cell, traffic=dict(cell.traffic,
                                                  schedule=schedule))
    monkeypatch.setattr(bench_cases, "tiny_cell", lambda name: cell)
    out = run("part1_4x2.cold", seconds=1.0)
    assert out["correct"], out["numbers"]
    got = [(r.request["start"], r.request["lam"], r.iters)
           for r in out["records"][:3]]
    assert got == [("cold", 0.01, 4), ("warm", 0.01, 1), ("cold", 0.02, 4)]
    # each solve is certified at its own lambda, so the gaps agree
    assert out["numbers"]["gap_report_err"]["value"] < 1e-6


def test_traffic_code_is_found_by_name(tmp_path):
    from chipbench.harness import load_traffic, requests, window_of
    (tmp_path / "burst.json").write_text(json.dumps({"loop": "open"}))
    (tmp_path / "burst.py").write_text(
        "def requests(cell, seed):\n"
        "    return [dict(lam=1.0, gap_target=0.5, K=2, start='cold')]\n"
        "def window(solve, reqs, seconds, previous):\n"
        "    return ['from burst.py']\n")
    mix, code = load_traffic("burst", root=tmp_path)
    cell = dataclasses.replace(tiny_cell("part1_4x2.cold"), traffic=mix,
                               traffic_code=code)
    assert requests(cell, 7) == [dict(lam=1.0, gap_target=0.5, K=2,
                                      start="cold")]
    assert window_of(cell)(None, [], 1.0, None) == ["from burst.py"]


def test_mix_that_needs_code_without_it_is_refused(tmp_path):
    from chipbench.harness import load_traffic, requests, window_of
    (tmp_path / "open.json").write_text(json.dumps(
        {"loop": "open", "clients": 1, "start": "cold"}))
    mix, code = load_traffic("open", root=tmp_path)
    assert code is None
    cell = dataclasses.replace(tiny_cell("part1_4x2.cold"), traffic=mix)
    with pytest.raises(ValueError, match="window of its own"):
        window_of(cell)
    bad = dataclasses.replace(cell, traffic=dict(
        mix, schedule=[{"start": "sideways"}]))
    with pytest.raises(ValueError, match="bad request"):
        requests(bad, 7)


def test_configuration_without_limits_is_refused():
    from chipbench.harness import run_cell
    cell = tiny_cell("part1_4x2.cold")
    cfg = dict(cell.config)
    del cfg["check"]
    with pytest.raises(ValueError, match="sets no limit"):
        run_cell(dataclasses.replace(cell, config=cfg), seed=1, seconds=0.1,
                 trace=False, devices=[], peaks={},
                 t_start=time.perf_counter())
