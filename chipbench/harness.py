"""The benchmark's harness: finds a cell's files by name, makes its data
from the seed, sets up the program, measures a window of back-to-back
steps, reads the device's peak memory, optionally a profiler trace, and
checks what the timed steps produced against the plain reference.

Everything that belongs to one configuration, traffic mix, solver or
per-layer metric sits in a file of its own, found by the name that
``BENCHMARK.json`` gives:

* ``configs/<file>.json``: a deployment's sizes; its ``generator`` names
  ``generators/<generator>.py``, which makes the data on the device;
* ``traffic/<traffic>.json``: the solver's settings and the mesh; its
  ``solver`` names ``solvers/<solver>.py``, which wires the program,
  counts the work of a step and compares a step with the reference;
* ``limits/<workload>.json``: each compared number's limit, with the
  readings it was set from;
* ``metrics/<metric>.py``: a reader with ``read(run)`` that returns the
  metric's value, or ``None`` where it finds nothing to read. ``run``
  holds the cell's ``cfg``, ``traffic`` and ``chips``; ``host``, the
  set-up timings; ``steps`` and ``window_s`` of the window; the chip's
  ``device_kind``; ``work()``, one step's ``(bytes, flops)`` on one chip;
  and ``trace``, the reduced trace (``tracecut.reduce``).
"""
from __future__ import annotations

import gc
import importlib.util
import json
import sys
import time
from pathlib import Path
from types import ModuleType, SimpleNamespace

ROOT = Path(__file__).resolve().parent
CHECKOUT = ROOT.parent
GIB = float(1 << 30)
MAX_STEP_TIMES = 200    # steps whose own times a run's detail records


class NoChip(RuntimeError):
    """JAX found no accelerator of the kind, or not as many as, the cell
    asks for."""


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


# ---------------------------------------------------------------- discovery

def load_benchmark(path: Path | None = None) -> dict:
    return json.loads(Path(path or CHECKOUT / "BENCHMARK.json").read_text())


def _named(items, name: str, what: str) -> dict:
    for it in items:
        if it["name"] == name:
            return it
    raise KeyError(f"no {what} named {name!r}; known: "
                   f"{sorted(i['name'] for i in items)}")


def load_module(path: Path) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"{path} not found")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{path.parent.name}_{path.stem}".replace("-", "_")
        .replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_data(directory: Path, name: str) -> dict:
    """The JSON file ``<directory>/<name>.json``."""
    p = directory / f"{name}.json"
    if not p.is_file():
        raise FileNotFoundError(f"no {directory.name} file named {name!r} "
                                f"in {directory}")
    return json.loads(p.read_text())


def resolve(workload: str, bench: dict, root: Path = ROOT) -> SimpleNamespace:
    """Every file a cell needs, loaded: its entry, configuration, traffic,
    limits, solver, generator and per-layer metric readers."""
    cell = _named(bench["workloads"], workload, "workload")
    centry = _named(bench["configs"], cell["config"], "config")
    cfg = json.loads((root.parent / centry["file"]).read_text())
    traffic = load_data(root / "traffic", cell["traffic"])
    limits = load_data(root / "limits", workload)
    per_layer = [m for m in bench["per_layer"]
                 if workload in m.get("workloads", [workload])]
    end_to_end = [m for m in bench["end_to_end"]
                  if workload in m.get("workloads", [workload])]
    return SimpleNamespace(
        cell=cell, cfg=cfg, traffic=traffic, limits=limits,
        solver=load_module(root / "solvers" / f"{traffic['solver']}.py"),
        generator=load_module(root / "generators" / f"{cfg['generator']}.py"),
        readers={m["name"]: load_module(root / "metrics" / f"{m['name']}.py")
                 for m in per_layer},
        per_layer=per_layer, end_to_end=end_to_end)


# ------------------------------------------------------------------- seeds

def seed_key(seed: int):
    """A PRNG key from any whole seed: JAX keeps 32 bits of an int seed,
    so the high word is folded in."""
    import jax
    import numpy as np
    key = jax.random.PRNGKey(np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


# --------------------------------------------------------------------- run

def devices_for(chips: int, require_tpu: bool = True):
    import jax
    devs = jax.devices()
    platform = devs[0].platform
    if require_tpu and platform != "tpu":
        raise NoChip(f"JAX platform is {platform!r}, not 'tpu': the "
                     f"benchmark measures a TPU and has no other mode")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips; JAX sees "
                     f"{len(devs)} {platform} device(s)")
    return devs[:chips]


def memory(devs) -> dict:
    """The fullest of ``devs`` by this process's peaks so far: the
    allocator's peak of buffers in use and the peak it reserved for
    programs' temporaries, which the TPU runtime keeps apart, in bytes.
    The CPU backend, which the harness's tests use, keeps no statistics
    and reads 0."""
    def one(d):
        m = d.memory_stats() or {}
        return {k: int(m.get(k, 0)) for k in
                ("peak_bytes_in_use", "peak_bytes_reserved")}
    return max((one(d) for d in devs), key=lambda m: sum(m.values()))


def run(workload: str, seed: int, seconds: float, trace: bool, t0: float,
        bench_path: Path | None = None, root: Path = ROOT,
        require_tpu: bool = True, tamper: str | None = None,
        trace_dir: str | None = None) -> dict:
    """One run of a cell; returns the result object the command prints.

    ``tamper``, for ``control.py`` and the harness's tests only, names an
    entry of the solver's ``TAMPERS`` that takes the timed step's place:
    the control, or a planted fault. ``trace_dir`` keeps the profiler
    trace there instead of in a temporary directory (``control.py``)."""
    import jax

    bench = load_benchmark(bench_path)
    spec = resolve(workload, bench, root)
    devs = devices_for(spec.cell["chips"], require_tpu)
    key = seed_key(seed)

    # set-up: data, program, compile
    nnz = spec.cfg["nnz_per_chip"] * spec.cell["chips"]
    with jax.profiler.TraceAnnotation("chipbench.generate"):
        idx, vals = spec.generator.generate(jax.random.fold_in(key, 0),
                                            spec.cfg, nnz)
        jax.block_until_ready((idx, vals))
    session = spec.solver.Session(spec.cfg, spec.traffic, devs, idx, vals,
                                  jax.random.fold_in(key, 1),
                                  jax.random.fold_in(key, 2))
    # the reference's copy of the data waits on the host, off the chip
    ref_data = jax.device_get((idx, vals))
    del idx, vals
    step = (session.step if tamper is None else
            spec.solver.TAMPERS[tamper](session, ref_data))
    state = session.state0
    host = dict(session.timings)

    profile_dir = None
    if trace:
        import tempfile
        profile_dir = (tempfile.TemporaryDirectory(prefix="chipbench_trace_")
                       if trace_dir is None else None)
        trace_dir = trace_dir or profile_dir.name
        jax.profiler.start_trace(trace_dir)

    mem_setup = memory(devs)
    # the window: steps back to back from the executable set-up compiled;
    # the first step's input and output stay on the device for the check
    first = None
    steps = 0
    t_start = time.perf_counter()
    ends = []
    setup_s = t_start - t0
    with jax.profiler.TraceAnnotation("chipbench.window"):
        while True:
            prev = state
            with jax.profiler.TraceAnnotation("chipbench.step"):
                state = step(state)
            with jax.profiler.TraceAnnotation("chipbench.wait"):
                jax.block_until_ready(state)
            steps += 1
            if steps <= MAX_STEP_TIMES:
                ends.append(time.perf_counter())
            if steps == 1:
                first = (prev, state)
            if time.perf_counter() - t_start >= seconds:
                break
    window_s = time.perf_counter() - t_start
    if trace:
        jax.profiler.stop_trace()
    mem = memory(devs)
    peak = sum(mem.values())
    first, last = jax.device_get((first, (prev, state)))

    # free the program's state before the reference runs on the chip
    del prev, state, step
    session.release()
    gc.collect()

    checked = [first] if steps == 1 else [first, last]
    checks, n_bad = session.check(ref_data, checked, spec.limits)
    correct = n_bad == 0

    runinfo = SimpleNamespace(
        cfg=spec.cfg, traffic=spec.traffic, chips=spec.cell["chips"],
        host=host, steps=steps, window_s=window_s, device_kind=
        devs[0].device_kind, work=session.step_work, trace=None)
    result = {"correct": correct, "attempted": steps, "failed": n_bad}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": int(peak)}
    if trace:
        from tracecut import reduce_dir
        runinfo.trace = reduce_dir(trace_dir)
        if profile_dir is not None:
            profile_dir.cleanup()
        values = {m["name"]: spec.readers[m["name"]].read(runinfo)
                  for m in spec.per_layer}
        device["busy_s"] = runinfo.trace["busy_s"]
        device["window_s"] = runinfo.trace["window_s"]
        wanted = spec.per_layer
    else:
        values = {spec.solver.STEP_METRIC: window_s / steps,
                  "hbm_peak_gib": peak / GIB, "setup_s": setup_s}
        wanted = spec.end_to_end
    result["metrics"] = {m["name"]: {"value": values[m["name"]],
                                     "unit": m["unit"]}
                         for m in wanted if values[m["name"]] is not None}
    result["device"] = device
    if trace:
        result["breakdown"] = runinfo.trace["breakdown"]
    result["detail"] = {
        "step_s": [b - a for a, b in zip([t_start] + ends, ends)],
        "memory": {"set_up": mem_setup, "window": mem},
        "reference": session.check_info}
    result["checks"] = checks
    log(f"{workload} seed {seed}: {steps} steps in {window_s:.4f} s, "
        f"set-up {setup_s:.4f} s (ingest {host.get('ingest_s', 0):.4f}, "
        f"compile {host.get('compile_s', 0):.4f}), peak "
        f"{peak / GIB:.4f} GiB; memory {result['detail']['memory']}; "
        f"reference {session.check_info}")
    for name, c in checks.items():
        log(f"check {name} {c['value']!r} limit {c['limit']!r} "
            f"{'ok' if c['value'] <= c['limit'] else 'FAIL'}")
    return result
