"""A configuration, traffic mix, limits file or per-layer metric is one new
file that ``harness.resolve`` finds by its name; and the command refuses
to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import harness
from conftest import BENCH, CHECKOUT


def test_new_files_are_found_by_name(tmp_path):
    root = tmp_path / "chipbench"
    shutil.copytree(BENCH, root, ignore=shutil.ignore_patterns(
        "tests", "__pycache__"))
    (root / "configs" / "enron4.json").write_text(json.dumps(
        {"generator": "function_tensor", "shape": [60, 50, 40, 30],
         "nnz_per_chip": 1000, "rank": 4, "lam": 0.01}))
    (root / "traffic" / "als-slow.json").write_text(json.dumps(
        {"solver": "als", "cg_iters": 5, "cg_tol": 1e-3, "mesh": None}))
    (root / "limits" / "enron4.als-slow.json").write_text(json.dumps(
        {"settled_margin": 1, "row_residual": {"limit": 1.0}}))
    (root / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return float(run.steps)\n")
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    bench["configs"].append({"name": "enron4", "source": "x",
                             "file": str(root / "configs" / "enron4.json"),
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "enron4.als-slow", "config": "enron4",
                               "traffic": "als-slow", "chips": 1,
                               "why": "x"})
    bench["per_layer"].append({"name": "steps_seen", "unit": "1",
                               "better": "higher", "source": "host_clock",
                               "layer": "launcher", "moves": "sweep_s",
                               "workloads": ["enron4.als-slow"]})
    spec = harness.resolve("enron4.als-slow", bench, root)
    assert spec.cfg["shape"] == [60, 50, 40, 30]
    assert spec.traffic["cg_iters"] == 5
    assert spec.limits["row_residual"]["limit"] == 1.0
    assert spec.readers["steps_seen"].read(
        type("Run", (), {"steps": 3})()) == 3.0
    assert spec.generator.generate.__module__.endswith("function_tensor")


def test_every_cell_of_the_benchmark_resolves():
    bench = harness.load_benchmark()
    for cell in bench["workloads"]:
        spec = harness.resolve(cell["name"], bench)
        assert spec.per_layer and spec.end_to_end


def _run_command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "function.als",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_a_cpu_and_prints_no_result():
    p = _run_command(CHECKOUT)
    assert p.returncode != 0
    assert "'cpu'" in p.stderr and "tpu" in p.stderr
    assert p.stdout.strip() == ""


def test_command_fails_without_the_program(tmp_path):
    shutil.copy(CHECKOUT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run_command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
