"""Paper-scale experiment harness: named (algorithm × loss × rank × dataset)
sweeps with per-sweep JSON metrics — the reproduction of the paper's study
shapes (Figures 6–8):

    python -m repro.launch.experiment --spec netflix-small --out results
    python -m repro.launch.experiment --list

Each spec streams its dataset through the out-of-core ingest
(``repro.data.streaming`` → ``CompletionDataset.from_stream``) with a
deterministic held-out split, then runs every requested (algorithm, loss)
pair through the existing solvers and ``RestartableLoop`` checkpointing
(per-sweep metric history rides in the checkpoint manifest, so an
interrupted experiment resumes with its metrics intact). Output is one JSON
file per spec: fit time, train/held-out RMSE, Poisson deviance and the
generalized-loss objective per sweep.

Algorithm × loss semantics (paper §2): ``ggn`` and ``gcp`` optimize the
requested loss natively (second-/first-order generalized-loss solvers);
``als``/``ccd``/``sgd`` are quadratic-update solvers — under a non-quadratic
loss they run their quadratic surrogate while the metrics report the
requested loss, which is exactly the paper's Fig.-8 comparison of quadratic
methods against Poisson methods on count data. The JSON records ``loss``
(evaluated), ``update_loss`` (optimized) and ``link`` (identity, or log for
the ``*_log`` losses, where held-out metrics evaluate exp(model) in rate
space).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time
from typing import Optional, Tuple

from repro import obs

ALGORITHMS = ("als", "ccd", "sgd", "ggn", "gcp")


@dataclasses.dataclass(frozen=True)
class ExperimentSpec:
    """One named experiment family (a paper figure's study shape)."""
    name: str
    dataset: str                       # "function" | "netflix" | "file"
    shape: Tuple[int, ...]
    nnz: int
    chunk_size: int
    rank: int
    sweeps: int
    algorithms: Tuple[str, ...] = ("als", "ccd", "sgd", "ggn")
    # "poisson_log" is the Poisson loss with log link — the well-posed
    # pairing for unconstrained solvers (identity-link "poisson" is
    # unbounded below for negative models and available via --losses)
    losses: Tuple[str, ...] = ("quadratic", "poisson_log")
    test_fraction: float = 0.1
    lam: float = 1e-4
    lr: float = 1e-3
    sample_rate: float = 0.5
    cg_iters: int = 20
    # initial Levenberg-Marquardt damping for ggn; None = per-loss default
    # (the fast-varying exp curvature of the *_log losses needs a stiff
    # start — the adaptive schedule relaxes it once steps are trusted)
    damping: Optional[float] = None
    seed: int = 0
    zipf_a: float = 1.1
    num_shards: int = 1
    file: Optional[str] = None         # triplet path for dataset="file"
    note: str = ""


SPECS = {s.name: s for s in [
    ExperimentSpec(
        "function-small", "function", (60, 50, 40), nnz=20_000,
        chunk_size=8_192, rank=8, sweeps=6,
        note="scaled-down Fig. 7a model problem"),
    ExperimentSpec(
        "netflix-small", "netflix", (150, 120, 40), nnz=40_000,
        chunk_size=8_192, rank=8, sweeps=6,
        note="scaled-down Fig. 7b/8 netflix-like ratings"),
    ExperimentSpec(
        "netflix-ci", "netflix", (80, 60, 20), nnz=15_000,
        chunk_size=4_096, rank=6, sweeps=4,
        note="nightly-CI shape: every algorithm under both losses"),
    ExperimentSpec(
        "paper-netflix", "netflix", (480_189, 17_770, 2_182),
        nnz=100_477_727, chunk_size=1 << 22, rank=32, sweeps=20,
        num_shards=256, lam=1e-2,
        note="full Netflix scale (paper Fig. 7b); needs a real mesh"),
    ExperimentSpec(
        "paper-function", "function", (16_384, 16_384, 16_384),
        nnz=10_000_000_000, chunk_size=1 << 24, rank=10, sweeps=10,
        num_shards=1024,
        note="paper headline: 10B nonzeros at ~2e-3 density on 256 nodes"),
]}


# ---------------------------------------------------------------------------
# solver construction (LOCAL ctx; the mesh path lives in launch/complete.py)
# ---------------------------------------------------------------------------

def make_solver(algorithm: str, loss_name: str, st, omega, factors,
                spec: ExperimentSpec):
    """Returns ``(state0, step, get_factors, update_loss_name, link)`` for
    one (algorithm, loss) run; ``step(i, state) -> state`` is jit-backed.

    ``als``/``ccd``/``sgd`` optimize their quadratic surrogate (identity
    link) whatever the evaluated loss; ``ggn``/``gcp`` optimize the
    requested loss — for ``*_log`` losses the model parameterizes
    log-rates, so held-out evaluation uses the exp (``log``) link."""
    import jax

    from repro.core import losses as LOSS
    from repro.core.completion import (als_sweep, ccd_sweep, gcp_adam_init,
                                       gcp_step, ggn_init, ggn_sweep,
                                       sgd_sweep)
    from repro.core.completion.ccd import residual_values

    loss = LOSS.LOSSES[loss_name]
    key = jax.random.PRNGKey(spec.seed + 1)

    link = ("log" if algorithm in ("ggn", "gcp")
            and loss_name.endswith("_log") else "identity")
    if algorithm == "als":
        fn = jax.jit(lambda s, o, fs: tuple(als_sweep(
            s, o, list(fs), spec.lam, cg_iters=spec.cg_iters)))
        return (tuple(factors),
                lambda i, fs: fn(st, omega, tuple(fs)),
                lambda state: list(state), "quadratic", link)
    if algorithm == "ccd":
        fn = jax.jit(lambda s, fs, rho: (lambda f, r_: (tuple(f), r_))(
            *ccd_sweep(s, list(fs), rho, spec.lam)))
        rho0 = residual_values(st, list(factors))
        return ((tuple(factors), rho0),
                lambda i, state: fn(st, state[0], state[1]),
                lambda state: list(state[0]), "quadratic", link)
    if algorithm == "sgd":
        sample = max(1024, int(spec.sample_rate * (st.nnz or st.cap)))
        fn = jax.jit(lambda k, s, fs: tuple(sgd_sweep(
            k, s, list(fs), spec.lam, spec.lr, sample)))
        return (tuple(factors),
                lambda i, fs: fn(jax.random.fold_in(key, i), st, tuple(fs)),
                lambda state: list(state), "quadratic", link)
    if algorithm == "ggn":
        damping = spec.damping
        if damping is None:
            damping = 10.0 if loss_name.endswith("_log") else 1e-5
        fn = jax.jit(lambda s, state: ggn_sweep(
            s, state, loss, spec.lam, cg_iters=spec.cg_iters))
        return (ggn_init(list(factors), damping=damping),
                lambda i, state: fn(st, state),
                lambda state: list(state.factors), loss_name, link)
    if algorithm == "gcp":
        fn = jax.jit(lambda s, fs, ad: (lambda f, a: (tuple(f), a))(
            *gcp_step(s, list(fs), loss, spec.lam, spec.lr, ad)))
        return ((tuple(factors), gcp_adam_init(list(factors))),
                lambda i, state: fn(st, tuple(state[0]), state[1]),
                lambda state: list(state[0]), loss_name, link)
    raise ValueError(f"unknown algorithm {algorithm!r}; "
                     f"choices: {ALGORITHMS}")


# ---------------------------------------------------------------------------
# runner
# ---------------------------------------------------------------------------

def run_experiment(spec: ExperimentSpec, out_dir: str = "experiments",
                   ckpt_root: Optional[str] = None,
                   algorithms: Optional[Tuple[str, ...]] = None,
                   losses: Optional[Tuple[str, ...]] = None,
                   spool_dir: Optional[str] = None,
                   trace: bool = False,
                   plan_cache: Optional[str] = None) -> dict:
    """Run every (algorithm, loss) pair of ``spec`` and write
    ``<out_dir>/experiment_<name>.json``; returns the report dict.
    ``trace=True`` enables obs tracing with a JSONL event stream at
    ``<out_dir>/trace_<name>.jsonl`` (per-sweep span trees additionally
    ride the metric history in the checkpoint manifest). ``plan_cache``
    autotunes the kernel tiles right after ingest (before any solver
    jit-traces) and persists the winners to that JSON file — a rerun of
    the same spec restores them with zero timings."""
    import jax

    if trace:
        os.makedirs(out_dir, exist_ok=True)
        obs.enable(jsonl=os.path.join(out_dir, f"trace_{spec.name}.jsonl"))
        obs.get_registry().reset()     # summary scoped to this experiment

    from repro.core import losses as LOSS
    from repro.core.completion.gcp import gcp_loss
    from repro.data import streaming
    from repro.data.pipeline import CompletionDataset
    from repro.runtime.fault_tolerance import RestartableLoop

    algorithms = tuple(algorithms or spec.algorithms)
    losses = tuple(losses or spec.losses)
    for a in algorithms:
        if a not in ALGORITHMS:
            raise ValueError(f"unknown algorithm {a!r}")
    for l in losses:
        if l not in LOSS.LOSSES:
            raise ValueError(f"unknown loss {l!r}")

    t_ing = time.perf_counter()
    chunks = streaming.make_stream(spec.dataset, spec.seed, spec.shape,
                                   spec.nnz, spec.chunk_size,
                                   path=spec.file, zipf_a=spec.zipf_a)
    ds = CompletionDataset.from_stream(
        chunks, spec.shape, num_shards=spec.num_shards,
        test_fraction=spec.test_fraction, spool_dir=spool_dir,
        bucket_modes=())
    ingest_seconds = time.perf_counter() - t_ing
    st, omega, test_st = ds.tensor, ds.omega, ds.test
    stats = ds.stats
    print(f"spec={spec.name} dataset={spec.dataset} shape={spec.shape} "
          f"train_nnz={st.nnz} test_nnz={test_st.nnz if test_st else 0} "
          f"dups_dropped={stats.duplicates_dropped} "
          f"ingest={ingest_seconds:.1f}s")

    plan_cache = plan_cache or os.environ.get("REPRO_PLAN_CACHE")
    tune_summary = None
    if plan_cache:
        # must precede make_solver: the jit'd sweeps bake the tile table in
        # at trace time (DESIGN.md §13)
        from repro.planner import tuner
        tune_key = jax.random.fold_in(jax.random.PRNGKey(spec.seed), 97)
        tks = jax.random.split(tune_key, len(spec.shape))
        tune_factors = [jax.random.normal(k, (d, spec.rank)) / spec.rank ** 0.5
                        for k, d in zip(tks, spec.shape)]
        tune_summary = tuner.ensure_tuned(st, tune_factors, omega=omega,
                                          cache_path=plan_cache)
        print(f"plan-cache: hits={tune_summary['hits']} "
              f"measured={tune_summary['measured']} "
              f"vmem_pruned={tune_summary['vmem_pruned']} "
              f"winners={tune_summary['winners']}")

    report = {
        "spec": {**dataclasses.asdict(spec), "shape": list(spec.shape)},
        "ingest": {
            "seconds": ingest_seconds,
            "nnz": stats.nnz,
            "test_nnz": int(test_st.nnz) if test_st is not None else 0,
            "chunks": stats.chunks,
            "entries_read": stats.entries_read,
            "duplicates_dropped": stats.duplicates_dropped,
            "nnz_rows": list(stats.nnz_rows),
            "shard_nnz": list(stats.shard_nnz),
            "busy_seconds": stats.ingest_seconds,
            "mnnz_per_s": stats.mnnz_per_s,
            "spills": stats.spills,
            "peak_rss_mb": stats.peak_rss_mb,
        },
        "runs": [],
    }
    if tune_summary is not None:
        report["plan_cache"] = {"path": plan_cache,
                                "hits": tune_summary["hits"],
                                "measured": tune_summary["measured"],
                                "vmem_pruned": tune_summary["vmem_pruned"],
                                "winners": tune_summary["winners"]}

    for loss_name in losses:
        loss = LOSS.LOSSES[loss_name]
        for algorithm in algorithms:
            import zlib
            run_key = jax.random.fold_in(
                jax.random.PRNGKey(spec.seed),
                zlib.crc32(f"{algorithm}/{loss_name}".encode()) % (2 ** 31))
            ks = jax.random.split(run_key, len(spec.shape))
            factors = [jax.random.normal(k, (d, spec.rank)) / spec.rank ** 0.5
                       for k, d in zip(ks, spec.shape)]
            state0, step, get_factors, update_loss, link = make_solver(
                algorithm, loss_name, st, omega, factors, spec)
            # the objective tracks what the solver actually minimizes (the
            # quadratic surrogate for als/ccd/sgd) — a meaningful monotone
            # quantity; the held-out metrics evaluate the requested loss
            upd_loss = LOSS.LOSSES[update_loss]
            obj_fn = jax.jit(
                lambda fs, _l=upd_loss: gcp_loss(st, list(fs), _l, spec.lam))

            metrics: list = []

            def loop_step(i, state, _m=metrics, _step=step,
                          _get=get_factors, _obj=obj_fn, _link=link):
                if i > 0 and not _m:
                    # resumed: rebuild the pre-failure metric history from
                    # the checkpoint manifest (RestartableLoop.last_metadata)
                    _m.extend(loop.last_metadata.get("metrics", [])[:i])
                t0 = time.perf_counter()
                with obs.span("sweep", algorithm=algorithm, loss=loss_name,
                              sweep=i) as sp:
                    state = _step(i, state)
                    sp.fence(jax.tree.leaves(state)[0])
                dt = time.perf_counter() - t0
                fs = _get(state)
                train = streaming.heldout_metrics(st, fs, link=_link)
                entry = {"sweep": i, "seconds": dt,
                         "objective": float(_obj(tuple(fs))),
                         "rmse_train": train["rmse"]}
                if test_st is not None:
                    test = streaming.heldout_metrics(test_st, fs, link=_link)
                    entry["rmse_test"] = test["rmse"]
                    entry["poisson_deviance_test"] = test["poisson_deviance"]
                if sp.record is not None:
                    # per-sweep span tree (nested planner/kernel spans when
                    # the solver ran any eager dispatch) rides the metric
                    # history into the checkpoint manifest, so a resumed
                    # experiment keeps its telemetry (DESIGN.md §11)
                    entry["trace"] = sp.record
                _m.append(entry)
                print(f"  [{algorithm}/{loss_name}] sweep {i:3d} "
                      f"{dt * 1e3:8.1f} ms  obj={entry['objective']:.5g}  "
                      f"rmse_test={entry.get('rmse_test', float('nan')):.5f}")
                return state

            ckpt_dir = os.path.join(
                ckpt_root or os.path.join(out_dir, "ckpt"),
                spec.name, f"{algorithm}-{loss_name}")
            loop = RestartableLoop(ckpt_dir, loop_step, ckpt_every=5,
                                   metadata_fn=lambda step, _m=metrics:
                                   {"metrics": _m})
            t0 = time.perf_counter()
            loop.run(state0, spec.sweeps)
            if not metrics:
                # resumed past the end (experiment already complete): no
                # sweep ran, so rebuild the history from the manifest
                metrics.extend(loop.last_metadata.get("metrics", []))
            report["runs"].append({
                "algorithm": algorithm, "loss": loss_name,
                "update_loss": update_loss, "link": link, "rank": spec.rank,
                "total_seconds": time.perf_counter() - t0,
                "sweeps": metrics,
                "final": metrics[-1] if metrics else None,
            })

    if trace:
        report["obs"] = obs.get_registry().summary()
        obs.emit_event({"kind": "experiment_summary", "spec": spec.name,
                        "obs": report["obs"]})
    os.makedirs(out_dir, exist_ok=True)
    out_path = os.path.join(out_dir, f"experiment_{spec.name}.json")
    with open(out_path, "w") as f:
        json.dump(report, f, indent=2)
    print(f"wrote {out_path} ({len(report['runs'])} runs)")
    if trace:
        obs.disable()
    return report


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--spec", default=None, choices=sorted(SPECS),
                    help="named experiment spec")
    ap.add_argument("--list", action="store_true",
                    help="list available specs and exit")
    ap.add_argument("--out", default="experiments", metavar="DIR")
    ap.add_argument("--algorithms", default=None,
                    help="comma list overriding the spec's algorithms")
    ap.add_argument("--losses", default=None,
                    help="comma list overriding the spec's losses")
    ap.add_argument("--sweeps", type=int, default=None)
    ap.add_argument("--rank", type=int, default=None)
    ap.add_argument("--nnz", type=int, default=None)
    ap.add_argument("--num-shards", type=int, default=None)
    ap.add_argument("--spool-dir", default=None,
                    help="spill ingest runs to disk (out-of-core)")
    ap.add_argument("--ckpt-root", default=None)
    ap.add_argument("--trace", action="store_true",
                    help="enable obs tracing; writes trace_<spec>.jsonl "
                         "next to the experiment JSON")
    ap.add_argument("--plan-cache", default=None, metavar="PATH",
                    help="on-disk kernel-tile plan cache (JSON): autotune "
                         "the Pallas tiles after ingest and persist the "
                         "winners (default: $REPRO_PLAN_CACHE; unset "
                         "disables tuning)")
    return ap


def main():
    args = build_parser().parse_args()
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.list or args.spec is None:
        for name, s in sorted(SPECS.items()):
            print(f"{name:16s} {s.dataset:9s} shape={s.shape} nnz={s.nnz} "
                  f"rank={s.rank} sweeps={s.sweeps} — {s.note}")
        if args.spec is None and not args.list:
            raise SystemExit("pick one with --spec NAME")
        return
    spec = SPECS[args.spec]
    overrides = {k: getattr(args, k) for k in
                 ("sweeps", "rank", "nnz", "num_shards")
                 if getattr(args, k) is not None}
    if overrides:
        spec = dataclasses.replace(spec, **overrides)
    run_experiment(
        spec, out_dir=args.out, ckpt_root=args.ckpt_root,
        algorithms=tuple(args.algorithms.split(",")) if args.algorithms
        else None,
        losses=tuple(args.losses.split(",")) if args.losses else None,
        spool_dir=args.spool_dir, trace=args.trace,
        plan_cache=args.plan_cache)


if __name__ == "__main__":
    main()
