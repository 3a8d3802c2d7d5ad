"""Distributed completion benchmark: LOCAL vs mesh sweeps on forced host
devices (DESIGN.md §9).

The forced-device XLA flag must be set before jax initializes, so the
measurements run in a SUBPROCESS (one jax init with 8 host devices); the
parent parses its ``name us`` lines into benchmark records. On a CPU
container the mesh numbers measure collective overhead, not speedup — the
point of the record is the trajectory of the distributed path itself.
"""
from __future__ import annotations

import os
import subprocess
import sys
import textwrap

from benchmarks.common import emit

_SCRIPT = textwrap.dedent("""
    import os, sys, time
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    sys.path.insert(0, "src")
    import jax, jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.completion import als_sweep
    from repro.core.distributed import DistLayout, LOCAL, make_mesh
    from repro.data.pipeline import CompletionDataset
    from repro.data import synthetic

    quick = bool(int(sys.argv[1]))
    dims = (48, 40, 32) if quick else (96, 80, 64)
    nnz = 8000 if quick else 40000
    r = 8
    sweeps = 3

    key = jax.random.PRNGKey(0)
    raw = synthetic.function_tensor(key, dims, nnz)
    mesh = make_mesh((4, 2), ("data", "model"))
    layout = DistLayout(mesh, ("data",), "model")
    ctx = layout.ctx
    ds = CompletionDataset(raw, key, mesh=mesh, data_axes=("data",))
    st, omega = ds.tensor, ds.omega
    ks = jax.random.split(key, 3)
    factors = tuple(jax.random.normal(k, (d, r)) / r ** 0.5
                    for k, d in zip(ks, dims))

    def timeit(fn, *args):
        jax.block_until_ready(fn(*args))          # compile
        ts = []
        for _ in range(sweeps):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*args))
            ts.append(time.perf_counter() - t0)
        return sorted(ts)[len(ts) // 2] * 1e6

    local_fn = jax.jit(lambda s, o, fs: tuple(
        als_sweep(s, o, list(fs), 1e-6, cg_iters=10, ctx=LOCAL)))
    print(f"dist_als_sweep_local {timeit(local_fn, st, omega, factors):.1f}")

    st_spec = layout.sparse_specs(st)
    f_spec = layout.factor_spec()
    mesh_fn = jax.jit(jax.shard_map(
        lambda s, o, fs: tuple(als_sweep(s, o, list(fs), 1e-6,
                                         cg_iters=10, ctx=ctx)),
        mesh=mesh, in_specs=(st_spec, st_spec, (f_spec,) * 3),
        out_specs=((f_spec,) * 3), check_vma=False))
    print(f"dist_als_sweep_mesh4x2 {timeit(mesh_fn, st, omega, factors):.1f}")
    print("BENCH-DIST-DONE")
""")


def run(quick: bool = False):
    import jax
    platform = jax.devices()[0].platform
    if platform != "cpu":
        # this process has initialized JAX and holds the accelerator, and
        # the child would time forced CPU devices under this host's name
        raise SystemExit(
            f"bench_distributed times 8 forced CPU host devices in a child "
            f"process; this host's JAX platform is {platform!r}, so the "
            f"numbers would not be device numbers. Run the mesh path on the "
            f"chip with `python chip_smoke.py --four-chips` instead.")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", _SCRIPT, str(int(quick))],
                         env=env, capture_output=True, text=True,
                         timeout=1200,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    if "BENCH-DIST-DONE" not in out.stdout:
        raise RuntimeError("distributed bench subprocess failed:\n"
                           + out.stdout + "\n---\n" + out.stderr)
    for line in out.stdout.splitlines():
        parts = line.split()
        if len(parts) == 2 and parts[0].startswith("dist_"):
            emit(parts[0], float(parts[1]),
                 "8 forced host devices; shard_map ALS via planner executor"
                 if "mesh" in parts[0] else "same problem, LOCAL ctx")
