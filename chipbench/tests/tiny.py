"""Small copies of the benchmark's cells for the CPU tests: the same
files, solver and limits, with each configuration's extents and nonzeros
cut so that a sweep takes milliseconds."""
import json
from pathlib import Path

from conftest import CHECKOUT

TINY = {"function-10b": {"shape": [300, 300, 300], "nnz_per_chip": 20000}}


def tiny_bench(tmp_path: Path) -> Path:
    """A ``BENCHMARK.json`` in ``tmp_path`` whose configurations are cut to
    ``TINY``."""
    bench = json.loads((CHECKOUT / "BENCHMARK.json").read_text())
    for c in bench["configs"]:
        cfg = json.loads((CHECKOUT / c["file"]).read_text())
        cfg.update(TINY[c["name"]])
        p = tmp_path / f"{c['name']}.json"
        p.write_text(json.dumps(cfg))
        c["file"] = str(p)
    path = tmp_path / "BENCHMARK.json"
    path.write_text(json.dumps(bench))
    return path


def cells(bench_path: Path):
    return [w["name"] for w in json.loads(bench_path.read_text())["workloads"]]
