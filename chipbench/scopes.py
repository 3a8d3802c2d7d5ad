"""Device time by the program's named scopes, and the ALS solver's CG step
counter, read from a traced window of sweeps.

    python3 chipbench/scopes.py --workload function.als --seed 5 --seconds 20
    python3 chipbench/scopes.py --workload function.als --seed 5 \
        --seconds 0.02 --small --out chipbench/tests/data

The program names its device work with ``repro.obs.scope``: the kernel
families ``tttp``, ``mttkrp``, ``cg_matvec`` and ``psum``, and the ALS
phases ``mode_<d>``, ``rhs``, ``matvec`` and ``cg_update``. Each name lands
in the ``op_name`` of the compiled operations traced under it
(``.../mode_0/while/body/matvec/mttkrp/scatter-add``). A profiler trace
names a device operation by its HLO instruction only; the compiled
program's text maps each instruction to its ``op_name`` (``op_names``).
XLA gives a fusion the ``op_name`` of its root instruction, so a fused
operation is put down to the scope of its root: a gather fused into a
scatter-add counts where the scatter-add was traced. Operations that XLA
adds itself (copies between loop iterations) carry no ``op_name`` and
count as ``unscoped``. ``cg_matvec`` (a Gram matvec the planner
dispatches, as GGN's ``matvec_path`` asks) and ``psum`` (a mesh) do not
occur in the one-chip ALS sweep this command compiles.

The command runs one cell as ``run.py --trace 1`` sets it up (one chip
only), but compiles ``als_sweep_stats``, which also returns each mode's CG
steps, and times a window of its sweeps under the profiler, keeping each
sweep's step counts on the device until the window ends. It prints one
JSON object: the trace's ``busy_s`` and ``window_s``, ``scopes`` (seconds
per mode and innermost scope, ``table``), the CG steps of every sweep,
and the figures that per-layer metrics are to read (``readings``). The
program's first sweep is checked against the reference, whose CG steps
are printed beside the program's. ``--small`` cuts the configuration to
the size of ``control.py``'s fixture; ``--out`` keeps the trace and the
``op_names`` map there, as the recorded fixture that
``tests/test_scopes.py`` reads. Like ``run.py``, it needs a TPU and exits
2 without one.

The readings time the ALS phases ``matvec`` and ``rhs``, not the kernel
families: inside a Gram matvec both halves gather the same rows of the
fixed factors, and XLA keeps one of the two gathers, with the ``op_name``
of the half it traced first (``tttp``). Which family holds the shared
gather is the compiler's choice, so ``tttp`` and ``mttkrp`` are split in
the table for reading, and only their union is a metric's denominator.
"""
from __future__ import annotations

import argparse
import json
import re
import sys
import time
from collections import defaultdict
from pathlib import Path

SCOPES = ("tttp", "mttkrp", "cg_matvec", "psum", "rhs", "matvec",
          "cg_update")
MODE = re.compile(r"mode_\d+")
UNSCOPED = "unscoped"
FIXTURE = "scoped_sweep"
_INSTRUCTION = re.compile(r'^\s*(?:ROOT )?%?([\w.\-]+) = .*'
                          r'metadata=\{[^}]*op_name="([^"]*)"', re.M)


def op_names(hlo_text: str) -> dict:
    """``{instruction name: op_name}`` of every instruction of a compiled
    program's text (``compiled.as_text()``) that carries an ``op_name``."""
    return dict(_INSTRUCTION.findall(hlo_text))


def op_seconds(path: str) -> dict:
    """Seconds of every device operation of the trace at ``path`` inside
    its window, by the operation names and rules of ``tracecut.reduce``
    (control-flow operations left out, averaged over the devices)."""
    import tracecut
    devices, spans = tracecut.planes(path)
    windows = [(s, e) for n, s, e in spans if n == tracecut.WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for _, ops in devices for _, s, _ in ops)
        hi = max(e for _, ops in devices for _, _, e in ops)
    out = defaultdict(float)
    for _, ops in devices:
        for n, s, e in ops:
            if not n.startswith(tracecut.CONTAINERS):
                out[n] += max(0.0, min(e, hi) - max(s, lo)) * 1e-9
    return {n: t / len(devices) for n, t in out.items()}


def scope_path(op: str, names: dict) -> list:
    """The scope names of a trace operation (``fusion.12 f32[40,5]``):
    the components of its instruction's ``op_name`` that are in
    ``SCOPES`` or name a mode (``mode_<d>``), outermost first."""
    name = names.get(op.split(" ")[0], "")
    return [c for c in name.split("/") if c in SCOPES or MODE.fullmatch(c)]


def table(seconds: dict, names: dict) -> dict:
    """Seconds per mode and innermost scope of ``SCOPES``: keys
    ``mode_<d>/<scope>`` (``mode_0/mttkrp``), ``<scope>`` outside every
    mode, ``mode_<d>`` for a mode's work under no other scope, and
    ``unscoped``."""
    out = defaultdict(float)
    for op, t in seconds.items():
        path = scope_path(op, names)
        modes = [c for c in path if MODE.fullmatch(c)]
        inner = [c for c in path if c in SCOPES]
        out["/".join(modes[-1:] + inner[-1:]) or UNSCOPED] += t
    return dict(out)


def under(seconds: dict, names: dict, *scopes: str) -> float:
    """Seconds of the operations under any of ``scopes``, at any depth."""
    return sum(t for op, t in seconds.items()
               if set(scope_path(op, names)) & set(scopes))


def kernel_work(nnz: int, rank: int, cg_steps) -> tuple:
    """``(bytes, flops)`` of the TTTP and MTTKRP passes of one sweep whose
    mode ``d`` ran ``cg_steps[d]`` CG steps: per mode one right-hand-side
    MTTKRP and ``cg_steps[d] + 1`` matvecs (``work.py``)."""
    import work
    order = len(cg_steps)
    mb, mf = work.matvec_work(nnz, order, rank)
    rb, rf = work.rhs_work(nnz, order, rank)
    passes = sum(int(n) + 1 for n in cg_steps)
    return (order * rb + passes * mb, order * rf + passes * mf)


def readings(seconds: dict, names: dict, busy_s: float, cg_steps: list,
             nnz: int, rank: int, device_kind: str) -> dict:
    """What per-layer metrics read, from a window of ``len(cg_steps)``
    sweeps: CG steps, the Gram matvecs' and the right-hand sides' device
    time per sweep, the kernels' share of their roofline at the CG steps
    counted, and the share of busy time under no scope."""
    import work
    sweeps = len(cg_steps)
    kernels = under(seconds, names, "tttp", "mttkrp")
    b = f = 0.0
    for steps in cg_steps:
        db, df = kernel_work(nnz, rank, steps)
        b, f = b + db, f + df
    least, _ = work.least_seconds(b, f, device_kind)
    return {
        "cg_steps_per_sweep": sum(map(sum, cg_steps)) / sweeps,
        "matvec_ms_per_sweep": 1e3 * under(seconds, names, "matvec") / sweeps,
        "rhs_ms_per_sweep": 1e3 * under(seconds, names, "rhs") / sweeps,
        "kernel_roofline_pct": 100.0 * least / kernels if kernels else None,
        "unscoped_pct": 100.0 * table(seconds, names).get(UNSCOPED, 0.0)
        / busy_s}


def compile_stats(session):
    """``als_sweep_stats`` compiled on a one-chip ``solvers/als.Session``'s
    data: ``factors -> (factors, cg_steps)`` as
    ``compiled(session.st, session.omega, factors)``."""
    import jax
    from repro.core.completion import als_sweep_stats
    lam, tol, iters = session.lam, session.cg_tol, session.cg_iters
    return jax.jit(lambda s, o, fs: (lambda f, n: (tuple(f), n))(
        *als_sweep_stats(s, o, list(fs), lam, cg_tol=tol, cg_iters=iters))
    ).lower(session.st, session.omega, session.state0).compile()


def measure(workload: str, seed: int, seconds: float, small: bool,
            out: str | None) -> dict:
    import tempfile

    import jax

    import harness
    import tracecut
    from control import FIXTURE_CONFIG

    bench = harness.load_benchmark()
    spec = harness.resolve(workload, bench)
    if spec.cell["chips"] != 1 or spec.traffic.get("mesh"):
        raise SystemExit(f"{workload}: scopes.py runs one-chip cells only")
    cfg = dict(spec.cfg, **(FIXTURE_CONFIG if small else {}))
    devs = harness.devices_for(1)
    key = harness.seed_key(seed)
    idx, vals = spec.generator.generate(jax.random.fold_in(key, 0), cfg,
                                        cfg["nnz_per_chip"])
    session = spec.solver.Session(cfg, spec.traffic, devs, idx, vals,
                                  jax.random.fold_in(key, 1),
                                  jax.random.fold_in(key, 2))
    ref_data = jax.device_get((idx, vals))
    del idx, vals
    compiled = compile_stats(session)
    names = op_names(compiled.as_text())

    state, counts = session.state0, []
    with tempfile.TemporaryDirectory(prefix="chipbench_scopes_") as tmp:
        jax.profiler.start_trace(tmp)
        t_start = time.perf_counter()
        with jax.profiler.TraceAnnotation(tracecut.WINDOW_SPAN):
            while True:
                prev = state
                with jax.profiler.TraceAnnotation("chipbench.step"):
                    state, n = compiled(session.st, session.omega, state)
                with jax.profiler.TraceAnnotation("chipbench.wait"):
                    jax.block_until_ready(state)
                counts.append(n)
                if len(counts) == 1:
                    first = (prev, state)
                if time.perf_counter() - t_start >= seconds:
                    break
        jax.profiler.stop_trace()
        path = tracecut.find_xplane(tmp)
        reduced = tracecut.reduce(path)
        secs = op_seconds(path)
        if out:
            Path(out).mkdir(parents=True, exist_ok=True)
            _keep(Path(out), path, names)
    cg_steps = [[int(x) for x in c] for c in jax.device_get(counts)]
    first = jax.device_get(first)
    del prev, state, compiled
    session.release()

    checks, bad = session.check(ref_data, [first], spec.limits)
    return {"workload": workload, "seed": seed, "small": small,
            "sweeps": len(cg_steps), "busy_s": reduced["busy_s"],
            "window_s": reduced["window_s"],
            "scopes": table(secs, names), "cg_steps": cg_steps,
            "reference_cg_steps": session.check_info[0]["cg_steps"],
            "correct": bad == 0, "checks": checks,
            **readings(secs, names, reduced["busy_s"], cg_steps,
                       session.nnz, session.rank, devs[0].device_kind)}


def _keep(out: Path, xplane: str, names: dict) -> None:
    """The trace and the ``op_names`` map as the recorded fixture, with
    the checkout's path blanked out as ``control.py fixture`` does."""
    import harness
    prefix = (str(harness.CHECKOUT) + "/").encode()
    blank = b"./" + b"x" * (len(prefix) - 3) + b"/"
    raw = Path(xplane).read_bytes()
    (out / f"{FIXTURE}.xplane.pb").write_bytes(raw.replace(prefix, blank))
    (out / f"{FIXTURE}.op_names.json").write_text(
        json.dumps(names, indent=0, sort_keys=True) + "\n")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="function.als")
    ap.add_argument("--seed", type=int, default=5)
    ap.add_argument("--seconds", type=float, default=20)
    ap.add_argument("--small", action="store_true",
                    help="cut the configuration to control.py's fixture")
    ap.add_argument("--out", help="keep the trace and op_names map here")
    args = ap.parse_args(argv)
    from run import configure_environment
    configure_environment()
    import harness
    try:
        res = measure(args.workload, args.seed, args.seconds, args.small,
                      args.out)
    except harness.NoChip as e:
        harness.log(f"chipbench: {e}")
        return 2
    print(json.dumps(res), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
