"""A whole run with the timed path broken underneath, or the control in
its place: the check has to come out not correct. The chip is not looked
for; the cells are cut small (``tiny.py``). Four host devices stand in for a four-chip host: the
benchmark has no four-chip cell yet, so the test adds one, the function
cell on the ``als.mesh4`` traffic."""
import json
import time

import pytest

import harness
from tiny import cells, tiny_bench


def _run(tmp_path, workload, tamper=None, seed=11):
    bench_path = tiny_bench(tmp_path)
    if workload not in cells(bench_path):
        bench = json.loads(bench_path.read_text())
        bench["workloads"].append({
            "name": workload, "config": "function-10b", "traffic": "als.mesh4",
            "chips": 4, "why": "x"})
        bench_path.write_text(json.dumps(bench))
    return harness.run(workload, seed, 0.5, False, time.perf_counter(),
                       bench_path=bench_path, require_tpu=False,
                       tamper=tamper)


CELLS = ["function.als", "function.als.mesh4"]


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(tmp_path, workload):
    res = _run(tmp_path, workload)
    assert res["correct"], res["checks"]
    assert list(res)[-1] == "checks"
    assert res["attempted"] >= 2 and res["failed"] == 0


@pytest.mark.parametrize("fault", ["control", "unchanged", "half",
                                   "altered"])
@pytest.mark.parametrize("workload", CELLS)
def test_fault_is_not_correct(tmp_path, workload, fault):
    res = _run(tmp_path, workload, tamper=fault)
    assert not res["correct"], res["checks"]
    assert res["failed"] > 0


def test_exchange_left_out_is_not_correct(tmp_path, monkeypatch):
    from repro.core.distributed import AxisCtx
    monkeypatch.setattr(AxisCtx, "psum_data", lambda self, x: x)
    res = _run(tmp_path, "function.als.mesh4")
    assert not res["correct"], res["checks"]
