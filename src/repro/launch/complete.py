"""Tensor-completion driver (the paper's workload):

    python -m repro.launch.complete --dataset function --algorithm als \
        --rank 10 --sweeps 10 [--nnz 200000 --dims 200,180,160] \
        [--mesh 4,2 --force-host-devices 8]

Algorithms: ``als`` (implicit-CG, quadratic loss), ``ccd``/``ccd_tttp``
(CCD++, einsum or TTTP-routed), ``sgd`` (sampled subgradient), ``gcp``
(first-order generalized-loss GD/Adam), and ``ggn`` (damped generalized
Gauss-Newton / Levenberg–Marquardt on the eq.-3 weighted Gram matvec —
second-order, any ``--loss``; see ``completion.gauss_newton`` and
DESIGN.md §8). Runs on a synthetic function tensor or Netflix-shaped
tensor, with checkpoint/restart via the fault-tolerant runner.

Distribution (DESIGN.md §4, §9): ``--mesh R,C`` builds a ``("data",
"model")`` mesh (shapes per ``--mesh-axes``), ingests the dataset through
``data.pipeline.CompletionDataset`` (nonzeros sharded over the data axes,
ingest-time CCSR bucket views attached), and runs every sweep under
``shard_map`` with the matching ``AxisCtx`` — the identical algorithm code,
contractions dispatched through ``planner.execute`` with the ctx's psums.
On CPU containers ``--force-host-devices N`` materializes N host devices
(must be set before jax initializes — hence the deferred imports below).
Without ``--mesh`` the same code runs with the LOCAL ctx — parallelism-
oblivious, as the paper prescribes."""
from __future__ import annotations

import argparse
import os
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dataset", default="function",
                    choices=["function", "netflix"])
    ap.add_argument("--algorithm", default="als",
                    choices=["als", "ccd", "ccd_tttp", "sgd", "gcp", "ggn"])
    ap.add_argument("--loss", default="quadratic")
    ap.add_argument("--dims", default="200,180,160")
    ap.add_argument("--nnz", type=int, default=200_000)
    ap.add_argument("--rank", type=int, default=10)
    ap.add_argument("--lam", type=float, default=1e-5)
    ap.add_argument("--sweeps", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--sample-rate", type=float, default=0.1)
    ap.add_argument("--cg-iters", type=int, default=20)
    ap.add_argument("--cg-tol", type=float, default=1e-4,
                    help="batched-CG relative residual tolerance (als/ggn)")
    ap.add_argument("--damping", type=float, default=1e-5,
                    help="initial Levenberg-Marquardt damping (ggn)")
    ap.add_argument("--matvec-path", default=None,
                    choices=["auto", "fused", "tttp_mttkrp", "sliced",
                             "dense"],
                    help="planner path for the ggn weighted Gram matvec "
                         "(DESIGN.md §8); default: direct kernel "
                         "composition. Under jit/shard_map 'fused' falls "
                         "back to the tttp_mttkrp composition (the cached "
                         "bucket pattern does not cross the tracer "
                         "boundary); the fused kernel itself is exercised "
                         "eagerly by benchmarks/bench_gauss_newton.py")
    ap.add_argument("--mesh", default=None, metavar="R,C",
                    help="mesh shape, e.g. '4,2' = 4-way data x 2-way "
                         "model; requires that many devices "
                         "(--force-host-devices on CPU)")
    ap.add_argument("--mesh-axes", default="data,model",
                    help="axis names matching --mesh (comma list)")
    ap.add_argument("--data-axes", default="data",
                    help="which mesh axes shard the nonzeros (comma list); "
                         "remaining axes column-shard the factors (model)")
    ap.add_argument("--force-host-devices", type=int, default=0,
                    metavar="N",
                    help="force N XLA host (CPU) devices before jax "
                         "initializes — the CPU stand-in for a real "
                         "multi-chip platform")
    ap.add_argument("--block-rows", type=int, default=None,
                    help="CCSR bucket granularity for the ingest-time "
                         "bucket views (default: PlannerConfig.block_rows)")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="on-disk kernel-tile plan cache (JSON). Autotunes "
                         "the Pallas kernel tiles at startup — before the "
                         "jit'd sweeps trace, which bake the tiles in — and "
                         "persists the measured winners; a second run of "
                         "the same workload re-installs them with zero "
                         "timings. Default: $REPRO_PLAN_CACHE; unset "
                         "disables tuning")
    ap.add_argument("--dump-factors", default=None, metavar="PATH",
                    help="write the final factor matrices to PATH. A .npz "
                         "path keeps the legacy flat format (keys "
                         "factor_0..factor_{N-1}); any other path becomes "
                         "a repro.checkpoint step directory with the fit "
                         "metadata (rank/shape/loss/link) in the manifest "
                         "— the format launch/serve_complete.py restores")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_completion_ckpt")
    return ap


def train_rmse(st, factors) -> float:
    """RMSE of the CP model over the valid entries of ``st``."""
    import jax.numpy as jnp
    from repro.core.tttp import multilinear_values

    model = multilinear_values(st, factors)
    d = (st.values - model) * st.mask
    n = jnp.maximum(jnp.sum(st.mask), 1)
    return float(jnp.sqrt(jnp.sum(jnp.square(d)) / n))


def main(argv=None) -> dict:
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``). Returns the run:
    ``history`` — one ``(sweep, seconds, rmse)`` per sweep run in this
    process —, ``compile_seconds``, the final ``factors`` and the training
    ``tensor``."""
    args = build_parser().parse_args(argv)
    if args.force_host_devices:
        flags = os.environ.get("XLA_FLAGS", "")
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count="
            f"{args.force_host_devices}").strip()
        os.environ.setdefault("JAX_PLATFORMS", "cpu")

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    # deferred: repro.kernels probes jax.devices() at import, which pins the
    # backend — XLA_FLAGS must be in the environment first
    import jax
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from repro.core import losses as LOSS
    from repro.core.completion import (als_sweep_stats, ccd_sweep,
                                       ccd_sweep_tttp, gcp_adam_init,
                                       gcp_step, ggn_init, ggn_sweep,
                                       sgd_sweep)
    from repro.core.completion.gcp import AdamState
    from repro.core.completion.ccd import residual_values
    from repro.core.completion.gauss_newton import GGNState
    from repro.core.distributed import DistLayout, LOCAL, make_mesh
    from repro.data import synthetic
    from repro.data.pipeline import CompletionDataset
    from repro.runtime.fault_tolerance import RestartableLoop

    if args.loss not in LOSS.LOSSES:
        raise SystemExit(f"unknown --loss {args.loss}; "
                         f"choices: {sorted(LOSS.LOSSES)}")

    if args.block_rows is not None:
        # retune the process-wide default so ingest (CompletionDataset) and
        # planner dispatch agree on the bucket granularity
        from repro.planner import PlannerConfig, set_default_config
        set_default_config(PlannerConfig(block_rows=args.block_rows))

    # ---- mesh / ctx ------------------------------------------------------
    mesh, ctx = None, LOCAL
    data_axes = ("data",)
    f_spec = None
    if args.mesh:
        mesh_shape = tuple(int(x) for x in args.mesh.split(","))
        axes = tuple(a.strip() for a in args.mesh_axes.split(","))
        need = int(np.prod(mesh_shape))
        have = len(jax.devices())
        if need > have:
            raise SystemExit(
                f"--mesh {args.mesh} needs {need} devices but only {have} "
                f"are visible; on CPU pass --force-host-devices {need}")
        mesh = make_mesh(mesh_shape, axes)
        data_axes = tuple(a for a in args.data_axes.split(",") if a)
        model_axes = [a for a in axes if a not in data_axes]
        model_axis = model_axes[0] if model_axes else None
        if args.algorithm in ("ccd", "ccd_tttp"):
            # CCD updates one column at a time — factors stay replicated
            # (no model axis), nonzeros/residuals shard over data
            model_axis = None
        layout = DistLayout(mesh, data_axes, model_axis)
        ctx = layout.ctx
        f_spec = (P(None, model_axis) if args.algorithm
                  not in ("ccd", "ccd_tttp") else P(None, None))
        print(f"mesh={dict(zip(axes, mesh_shape))} data_axes={data_axes} "
              f"model_axis={model_axis} devices={have}")
    elif len(jax.devices()) > 1:
        print(f"note: {len(jax.devices())} devices visible but no --mesh "
              f"given — running LOCAL (single-device semantics); pass "
              f"--mesh to distribute")

    # ---- dataset ingest (shared shuffle/pad/shard + bucket views) --------
    shape = tuple(int(x) for x in args.dims.split(","))
    key = jax.random.PRNGKey(0)
    if args.dataset == "function":
        raw = synthetic.function_tensor(key, shape, args.nnz)
    else:
        raw = synthetic.netflix_like(key, shape, args.nnz)
    # every sweep below is jit'd/shard_map'd, where the host-side bucket
    # pattern cache cannot cross the tracer boundary — skip the ingest
    # build (bucket_modes=()); eager consumers (benchmarks, interactive
    # solves) keep CompletionDataset's default per-mode build
    ds = CompletionDataset(raw, key, mesh=mesh, data_axes=data_axes,
                           block_rows=args.block_rows, bucket_modes=())
    st, omega = ds.tensor, ds.omega

    r = args.rank
    ks = jax.random.split(key, len(shape))
    factors = [jax.random.normal(k, (d, r)) / r ** 0.5
               for k, d in zip(ks, shape)]
    nd = len(shape)
    print(f"dataset={args.dataset} shape={shape} nnz={st.nnz} rank={r} "
          f"algorithm={args.algorithm} loss={args.loss}")

    # ---- kernel-tile autotuning (must precede the jit'd sweeps: the tile
    # table is read at trace time, so tuning later would not retile them) --
    plan_cache = args.plan_cache or os.environ.get("REPRO_PLAN_CACHE")
    if plan_cache:
        if mesh is not None:
            print("note: --plan-cache tuning skipped under --mesh (tiles "
                  "are tuned on single-device eager kernels)")
        else:
            from repro.planner import tuner
            summary = tuner.ensure_tuned(st, factors, omega=omega,
                                         cache_path=plan_cache)
            print(f"plan-cache: hits={summary['hits']} "
                  f"measured={summary['measured']} "
                  f"vmem_pruned={summary['vmem_pruned']} "
                  f"winners={summary['winners']}")

    loss = LOSS.LOSSES[args.loss]
    sample = max(1024, int(args.sample_rate * st.nnz))

    def wrap(fn, in_specs, out_specs):
        """jit, under shard_map when a mesh is configured."""
        if mesh is None:
            return jax.jit(fn)
        return jax.jit(jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                     out_specs=out_specs, check_vma=False))

    if mesh is not None:
        st_spec = layout.sparse_specs(st)
        fs_spec = (f_spec,) * nd
    else:
        st_spec = fs_spec = None

    # an ALS sweep also returns each mode's CG steps (its solver counter),
    # printed on the sweep's line
    counts_steps = args.algorithm == "als"
    if args.algorithm == "als":
        fn = wrap(lambda s, o, fs: (lambda f, n: (tuple(f), n))(
                      *als_sweep_stats(s, o, list(fs), args.lam,
                                       cg_tol=args.cg_tol,
                                       cg_iters=args.cg_iters, ctx=ctx)),
                  (st_spec, st_spec, fs_spec), (fs_spec, P()))
        state0 = tuple(factors)
        call_args = lambda i, fs: (st, omega, tuple(fs))
    elif args.algorithm in ("ccd", "ccd_tttp"):
        sweep = ccd_sweep if args.algorithm == "ccd" else ccd_sweep_tttp
        fn = wrap(lambda s, fs, rho: (lambda f, r_: (tuple(f), r_))(
                      *sweep(s, list(fs), rho, args.lam, ctx=ctx)),
                  (st_spec, fs_spec, None if mesh is None
                   else st_spec.values),
                  (fs_spec, None if mesh is None else st_spec.values))
        rho0 = residual_values(st, factors)
        state0 = (tuple(factors), rho0)
        call_args = lambda i, stt: (st, tuple(stt[0]), stt[1])
    elif args.algorithm == "sgd":
        fn = wrap(lambda k, s, fs: tuple(sgd_sweep(
                      k, s, list(fs), args.lam, args.lr, sample, ctx=ctx)),
                  (P() if mesh is not None else None, st_spec, fs_spec),
                  fs_spec)
        state0 = tuple(factors)
        call_args = lambda i, fs: (jax.random.fold_in(key, i), st, tuple(fs))
    elif args.algorithm == "ggn":
        if args.matvec_path == "fused":
            print("note: under jit/shard_map the 'fused' matvec path falls "
                  "back to the tttp_mttkrp composition (see --help)")
        matvec_path = args.matvec_path
        if matvec_path in ("fused", "dense") and ctx.model is not None:
            print(f"note: matvec path {matvec_path!r} cannot insert the "
                  f"inter-half psum(model); using the cost-model choice")
            matvec_path = "auto"
        fn = wrap(lambda s, stt: ggn_sweep(
                      s, stt, loss, args.lam, cg_tol=args.cg_tol,
                      cg_iters=args.cg_iters, ctx=ctx,
                      matvec_path=matvec_path),
                  (st_spec, None if mesh is None
                   else GGNState(fs_spec, P())),
                  None if mesh is None else GGNState(fs_spec, P()))
        state0 = ggn_init(factors, damping=args.damping)
        call_args = lambda i, stt: (st, stt)
    else:  # gcp
        ad0 = gcp_adam_init(factors)
        ad_spec = None if mesh is None else AdamState(
            [f_spec] * nd, [f_spec] * nd, P())
        fn = wrap(lambda s, fs, ad: (lambda f, a: (tuple(f), a))(
                      *gcp_step(s, list(fs), loss, args.lam, args.lr, ad,
                                ctx=ctx)),
                  (st_spec, fs_spec, ad_spec), (fs_spec, ad_spec))
        state0 = (tuple(factors), ad0)
        call_args = lambda i, stt: (st, tuple(stt[0]), stt[1])

    # compile ahead of the first sweep so its time is reported on its own;
    # the jit call below reuses the executable
    t0 = time.perf_counter()
    fn.lower(*call_args(0, state0)).compile()
    compile_s = time.perf_counter() - t0
    print(f"compile {compile_s:.2f} s")

    def get_factors(state):
        if isinstance(state, GGNState):
            return list(state.factors)
        if isinstance(state, tuple) and isinstance(state[0], tuple):
            return list(state[0])
        return list(state)

    hist = []

    def loop_step(i, state):
        t0 = time.perf_counter()
        state = fn(*call_args(i, state))
        jax.block_until_ready(state)
        dt = time.perf_counter() - t0
        note = ""
        if counts_steps:
            state, steps = state
            note = f"  cg_steps={np.asarray(steps).tolist()}"
        e = train_rmse(st, get_factors(state))
        hist.append((i, dt, e))
        print(f"sweep {i:3d}  {dt*1e3:8.1f} ms  rmse={e:.6f}{note}")
        return state

    loop = RestartableLoop(args.ckpt_dir, loop_step, ckpt_every=5)
    final = loop.run(state0, args.sweeps)
    if hist:
        print(f"final rmse={hist[-1][2]:.6f} "
              f"(mean sweep {sum(h[1] for h in hist)/len(hist)*1e3:.1f} ms)")
    else:  # checkpoint resume found every sweep already done
        print(f"final rmse={train_rmse(st, get_factors(final)):.6f} "
              f"(all {args.sweeps} sweeps restored from {args.ckpt_dir})")
    if args.dump_factors:
        fs = get_factors(final)
        if args.dump_factors.endswith(".npz"):
            np.savez(args.dump_factors,
                     **{f"factor_{d}": np.asarray(f)
                        for d, f in enumerate(fs)})
        else:
            from repro import checkpoint as ckpt
            link = "log" if args.loss.endswith("_log") else "identity"
            ckpt.save(args.dump_factors, args.sweeps,
                      {f"factor_{d}": f for d, f in enumerate(fs)},
                      metadata={"kind": "cp_factors", "rank": r,
                                "shape": list(shape),
                                "algorithm": args.algorithm,
                                "loss": args.loss, "link": link,
                                "dataset": args.dataset,
                                "nnz": int(st.nnz), "sweeps": args.sweeps})
        print(f"wrote factors to {args.dump_factors}")
    return {"history": hist, "compile_seconds": compile_s,
            "factors": get_factors(final), "tensor": st}


if __name__ == "__main__":
    main()
