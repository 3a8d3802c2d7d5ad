"""ALS through the program, wired as its launcher ``repro.launch.complete``
wires it: ``als_sweep`` under ``jax.jit`` on one chip, under
``jax.jit(jax.shard_map(...))`` with a ``DistLayout`` and its
``sparse_specs`` on a mesh, fed by ``CompletionDataset(...,
bucket_modes=())``. One step is one sweep over every mode.

The check runs after the window, on the first chip, with the program's
state freed: each checked sweep is redone mode by mode by the plain
reference (``reference.py``) from the same inputs, on the benchmark's own
copy of the data, and the gaps are held to the cell's limits.

``TAMPERS`` holds what ``control.py`` and the harness's tests put in the
timed step's place: the control (the reference in bfloat16) and the
faults the check has to catch.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp

import reference
import work

STEP_METRIC = "sweep_s"


class Session:
    """The program set up for a window: ingested data, initial factors and
    the compiled sweep. ``timings`` holds ``ingest_s`` and ``compile_s``."""

    def __init__(self, cfg: dict, traffic: dict, devices, idx, vals,
                 factor_key, ingest_key):
        from jax.sharding import NamedSharding

        from repro.core.completion import als_sweep
        from repro.core.distributed import DistLayout, LOCAL, make_mesh
        from repro.core.sparse_tensor import SparseTensor
        from repro.data.pipeline import CompletionDataset

        self.shape = tuple(cfg["shape"])
        self.rank, self.lam = cfg["rank"], cfg["lam"]
        self.cg_iters, self.cg_tol = traffic["cg_iters"], traffic["cg_tol"]
        self.nnz = int(idx.shape[0])
        self.chips = len(devices)
        self.timings = {}
        self.check_info = None

        mesh, ctx, data_axes = None, LOCAL, ("data",)
        if traffic.get("mesh"):
            axes = tuple(traffic["mesh_axes"])
            data_axes = tuple(traffic["data_axes"])
            mesh = make_mesh(tuple(traffic["mesh"]), axes)
            model = [a for a in axes if a not in data_axes]
            layout = DistLayout(mesh, data_axes, model[0] if model else None)
            ctx = layout.ctx

        raw = SparseTensor.from_coo(idx, vals, self.shape)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.ingest"):
            ds = CompletionDataset(raw, ingest_key, mesh=mesh,
                                   data_axes=data_axes, bucket_modes=())
            jax.block_until_ready((ds.tensor, ds.omega))
        self.timings["ingest_s"] = time.perf_counter() - t
        self.st, self.omega = ds.tensor, ds.omega
        del raw, ds

        factors = _init_factors(factor_key, self.shape, self.rank)
        lam, tol, iters = self.lam, self.cg_tol, self.cg_iters
        sweep = lambda s, o, fs: tuple(als_sweep(
            s, o, list(fs), lam, cg_tol=tol, cg_iters=iters, ctx=ctx))
        if mesh is None:
            fn = jax.jit(sweep)
        else:
            st_spec = layout.sparse_specs(self.st)
            f_spec = layout.factor_spec()
            fs_spec = (f_spec,) * len(self.shape)
            fn = jax.jit(jax.shard_map(
                sweep, mesh=mesh, in_specs=(st_spec, st_spec, fs_spec),
                out_specs=fs_spec, check_vma=False))
            factors = tuple(jax.device_put(f, NamedSharding(mesh, f_spec))
                            for f in factors)
        self.state0 = tuple(factors)
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("chipbench.compile"):
            self._compiled = fn.lower(self.st, self.omega,
                                      self.state0).compile()
        self.timings["compile_s"] = time.perf_counter() - t

    def step(self, factors):
        """One ALS sweep, dispatched (not waited for)."""
        return self._compiled(self.st, self.omega, factors)

    def step_work(self) -> tuple:
        """``(bytes, flops)`` of one sweep on one chip, at the CG bound."""
        return work.sweep_work(self.nnz // self.chips, len(self.shape),
                               self.rank, self.cg_iters)

    def release(self) -> None:
        self.st = self.omega = self.state0 = self._compiled = None

    def check(self, data, pairs, limits: dict):
        """Check each ``(factors in, factors out)`` pair against the
        reference (``reference.check_sweep``). Returns ``({name: {"value",
        "limit"}}, number of pairs out of a limit)``."""
        idx, vals = (jax.device_put(a) for a in data)
        worst, bad, self.check_info = -float("inf"), 0, []
        for f_in, f_out in pairs:
            got = reference.check_sweep(
                idx, vals, f_in, f_out, self.lam, self.cg_tol, self.cg_iters,
                limits["settled_margin"])
            worst = max(worst, got["row_residual"])
            bad += not got["row_residual"] <= limits["row_residual"]["limit"]
            self.check_info.append(
                {k: got[k] for k in ("rows", "rows_all", "cg_steps",
                                     "by_margin", "rows_by_margin")})
        return {"row_residual": {"value": worst, "limit":
                                 limits["row_residual"]["limit"]}}, bad


@functools.partial(jax.jit, static_argnums=(1, 2))
def _init_factors(key, shape, rank):
    """Initial factors ``normal / sqrt(R)``, made on the device in one
    call."""
    ks = jax.random.split(key, len(shape))
    return tuple(jax.random.normal(k, (d, rank)) / rank ** 0.5
                 for k, d in zip(ks, shape))


def _control(session, data):
    """The control: the plain reference in bfloat16 in the program's
    place, one sweep per step."""
    idx, vals = (jax.device_put(a) for a in data)

    def step(fs):
        out = reference.sweep(idx, vals, fs, session.lam, session.cg_tol,
                              session.cg_iters, jnp.bfloat16)
        return tuple(f.astype(jnp.float32) for f in out)
    return step


def _unchanged(session, data):
    """A step that returns its state unchanged."""
    return lambda fs: fs


def _half(session, data):
    """Half of the nonzeros left out of every sweep."""
    def cut(st):
        keep = jnp.arange(st.cap) % 2 == 0
        return type(st)(st.indices, st.values, st.valid & keep, st.shape,
                        st.nnz)
    st, omega = cut(session.st), cut(session.omega)
    return lambda fs: session._compiled(st, omega, fs)


def _altered(session, data):
    """One answer altered where it is produced: a row of the second
    mode's factor negated in the sweep's output."""
    def step(fs):
        fs = list(session.step(fs))
        fs[1] = fs[1].at[0].multiply(-1.0)
        return tuple(fs)
    return step


TAMPERS = {"control": _control, "unchanged": _unchanged, "half": _half,
           "altered": _altered}
