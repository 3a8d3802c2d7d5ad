"""Readings behind the limits of the cells' check, taken on the chip.

    python3 chipbench/control.py readings --workload function.als \
        --seeds 11,12,13 --seconds 5 --kinds program,control,unchanged,half
    python3 chipbench/control.py trace --workload function.als --seed 5 \
        --seconds 20 --out <directory>
    python3 chipbench/control.py fixture --out chipbench/tests/data

``readings``: for each seed and each kind, one whole run of the cell at
its own size (``harness.run``) with a window of ``--seconds``, in one
process; it prints one JSON line per run with ``correct``, the checks
beside their limits and the reference's detail (the compared number at
every settling margin). ``program`` is the run as the benchmark makes it;
every other kind names an entry of the solver's ``TAMPERS`` put in the
timed step's place: ``control``, the reference in bfloat16, and the
planted faults. The program's readings give a limit its lower end; the
control's its upper end.

``trace``: one traced run of the cell (as ``run.py --trace 1``) that keeps
the profiler's trace in ``--out`` for reading by hand.

``fixture``: a traced window of a few milliseconds over a small
function tensor (3,000^3, 40,000 nonzeros, rank 10), kept in ``--out``
as the recorded trace that ``tests/test_tracecut.py`` reads,
with the checkout's path blanked out.

Like ``run.py``, it needs a TPU and exits 2 without one.
"""
from __future__ import annotations

import argparse
import json
import sys
import tempfile
import time
from pathlib import Path

from run import T0, configure_environment

FIXTURE_CONFIG = {"shape": [3000, 3000, 3000], "nnz_per_chip": 40000}


def readings(workload: str, seeds, seconds: float, kinds) -> None:
    import harness
    for seed in seeds:
        for kind in kinds:
            t = time.perf_counter()
            res = harness.run(workload, seed, seconds, False, t,
                              tamper=None if kind == "program" else kind)
            print(json.dumps({
                "workload": workload, "seed": seed, "kind": kind,
                "correct": res["correct"], "steps": res["attempted"],
                "metrics": res["metrics"], "detail": res["detail"],
                "checks": res["checks"],
                "run_s": time.perf_counter() - t}), flush=True)


def trace(workload: str, seed: int, seconds: float, out: str) -> None:
    import harness
    Path(out).mkdir(parents=True, exist_ok=True)
    print(json.dumps(harness.run(workload, seed, seconds, True, T0,
                                 trace_dir=out)), flush=True)


def fixture(out: str) -> None:
    import harness
    bench = harness.load_benchmark()
    centry = next(c for c in bench["configs"]
                  if c["name"] == "function-10b")
    cfg = json.loads((harness.CHECKOUT / centry["file"]).read_text())
    cfg.update(FIXTURE_CONFIG)
    with tempfile.TemporaryDirectory() as tmp:
        cfg_path = Path(tmp) / "function-fixture.json"
        cfg_path.write_text(json.dumps(cfg))
        centry["file"] = str(cfg_path)
        bench_path = Path(tmp) / "BENCHMARK.json"
        bench_path.write_text(json.dumps(bench))
        trace_dir = Path(tmp) / "trace"
        res = harness.run("function.als", 5, 0.02, True, T0,
                          bench_path=bench_path, trace_dir=str(trace_dir))
        import tracecut
        Path(out).mkdir(parents=True, exist_ok=True)
        # the trace names source files by absolute path; the recorded copy
        # names the checkout by a placeholder of the same length, which
        # keeps the protobuf's string lengths valid
        prefix = (str(harness.CHECKOUT) + "/").encode()
        blank = b"./" + b"x" * (len(prefix) - 3) + b"/"
        raw = Path(tracecut.find_xplane(str(trace_dir))).read_bytes()
        (Path(out) / "tiny_sweep.xplane.pb").write_bytes(
            raw.replace(prefix, blank))
        (Path(out) / "tiny_sweep.reduced.json").write_text(
            json.dumps(tracecut.reduce_dir(str(trace_dir)), indent=1) + "\n")
    print(json.dumps(res), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("mode", choices=("readings", "trace", "fixture"))
    ap.add_argument("--workload", default="function.als")
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--kinds", default="program,control")
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--out", help="directory for the trace (trace, fixture)")
    args = ap.parse_args(argv)
    configure_environment()
    import harness
    try:
        if args.mode == "readings":
            readings(args.workload, [int(s) for s in args.seeds.split(",")],
                     args.seconds, args.kinds.split(","))
        elif not args.out:
            ap.error(f"{args.mode} needs --out")
        elif args.mode == "trace":
            trace(args.workload, args.seed, args.seconds, args.out)
        else:
            fixture(args.out)
    except harness.NoChip as e:
        harness.log(f"chipbench: {e}")
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
