"""The harness end to end on the CPU at scale 10: set-up, window, check and
the trace's reduction, for each cell and traced or not.  A CPU run measures
no device metric, so nothing here reads or prints one."""

import json
import os
import shutil
import time

import pytest

from bench import harness

SCALE = 10


@pytest.mark.parametrize("traced", [False, True], ids=["untraced", "traced"])
@pytest.mark.parametrize("workload", ["kron-s20.fff", "urand-s20.fff"])
def test_run_end_to_end(workload, traced):
    cell = harness.load_cell(workload)
    o = harness.run(cell, 2**31 + 17, 0.5, traced, t_start=time.perf_counter(), scale=SCALE)
    assert o.correct(), o.numbers
    assert o.error is None and len(o.requests) >= 1
    assert all(r.iterations == cell.traffic["steps"] for r in o.requests)
    assert all(r.session_reuse and r.prepare_s == 0.0 for r in o.requests)
    assert {r.backend for r in o.requests} == {"restarted"}
    assert o.t_close - o.t_open >= 0.5 and o.setup_s > 0
    assert set(harness.end_to_end(o)) == {m["name"] for m in cell.end_to_end}
    if traced:
        assert o.trace.requests == len(o.requests)
        assert o.trace.window_s == pytest.approx(o.t_close - o.t_open, rel=0.05)


def test_fdf_cell_is_a_traffic_file_and_an_entry(tmp_path):
    """``kron-s20.fdf`` needs ``bench/traffic/sweep16.fdf.json`` and one
    ``BENCHMARK.json`` entry, nothing else."""
    root = os.path.dirname(harness.BENCH)
    shutil.copytree(harness.BENCH, tmp_path / "bench")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    bench["workloads"].append(
        {"name": "kron-s20.fdf", "config": "gap-kron-s20", "traffic": "sweep16.fdf",
         "chips": 1, "why": "the paper's mixed precision on the kron matrix"}
    )
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    with open(os.path.join(harness.BENCH, "traffic", "sweep16.fff.json")) as f:
        fdf = json.load(f)
    fdf["request"].update(policy="FDF", tol=1.4901161193847656e-08)
    fdf["control"] = {"policy": "FFF"}
    (tmp_path / "bench" / "traffic" / "sweep16.fdf.json").write_text(json.dumps(fdf))

    cell = harness.load_cell("kron-s20.fdf", root=str(tmp_path))
    assert cell.traffic["request"]["policy"] == "FDF"
    o = harness.run(cell, 4, 0.0, False, t_start=time.perf_counter(), scale=SCALE)
    assert o.correct(), o.numbers
    assert [r.iterations for r in o.requests] == [16]
