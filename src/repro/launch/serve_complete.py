"""Serving driver on frozen factors (DESIGN.md §14):

    python -m repro.launch.complete --dataset netflix --rank 8 --sweeps 3 \
        --dump-factors /tmp/serve_ckpt
    python -m repro.launch.serve_complete --factors /tmp/serve_ckpt \
        --num-queries 100000 --batch-size 1024 --topk 10 --foldin-users 32

Restores the checkpoint (``repro.checkpoint`` step directory or legacy
``.npz``), then drives the three serving endpoints through
``repro.serve.ServeEngine``:

* a load generator streaming ``--num-queries`` random entry-scoring
  queries in ``--batch-size`` batches, reporting QPS and p50/p95/p99
  per-batch latency;
* ``--topk K`` retrievals over ``--topk-mode`` for ``--topk-users``
  sampled queries;
* ``--foldin-users`` cold-user fold-ins with ``--foldin-nnz``-entry
  synthetic histories (damped one-row ALS on the frozen factors).

``--verify`` asserts correctness before any timing is trusted: served
scores must match ``core.tttp.multilinear_values`` to 1e-6 and fold-in
rows must match an explicit (Gram-forming) one-row ALS solve to 1e-4 —
the process exits nonzero otherwise, which is what the ``serve-smoke``
CI job gates on. ``--json`` writes the full report.
"""
from __future__ import annotations

import argparse
import json
import sys
import time


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser()
    ap.add_argument("--factors", required=True, metavar="PATH",
                    help="checkpoint directory (repro.checkpoint step dirs) "
                         "or .npz written by complete.py --dump-factors")
    ap.add_argument("--step", type=int, default=None,
                    help="checkpoint step to restore (default: newest)")
    ap.add_argument("--link", default=None, choices=["identity", "log"],
                    help="prediction link; default: the checkpoint "
                         "metadata's link (identity for .npz)")
    ap.add_argument("--num-queries", type=int, default=10_000)
    ap.add_argument("--batch-size", type=int, default=1024)
    ap.add_argument("--score-path", default=None,
                    choices=["all_at_once", "sliced", "pairwise", "dense"],
                    help="force the scoring contraction through a planner "
                         "TTTP path (default: direct gather chain)")
    ap.add_argument("--topk", type=int, default=0, metavar="K",
                    help="also run top-k retrieval (0 disables)")
    ap.add_argument("--topk-mode", type=int, default=1,
                    help="mode retrieved over (the 'items')")
    ap.add_argument("--topk-users", type=int, default=32)
    ap.add_argument("--topk-block", type=int, default=4096,
                    help="item-factor rows per streaming top-k block")
    ap.add_argument("--foldin-users", type=int, default=0, metavar="B",
                    help="fold in B cold users (0 disables)")
    ap.add_argument("--foldin-mode", type=int, default=0,
                    help="mode the cold rows belong to (the 'users')")
    ap.add_argument("--foldin-nnz", type=int, default=16,
                    help="history length per cold user")
    ap.add_argument("--foldin-lam", type=float, default=1e-2,
                    help="fold-in ridge damping λ")
    ap.add_argument("--matvec-path", default=None,
                    choices=["tttp_mttkrp", "sliced", "dense"],
                    help="planner CG_MATVEC path for the fold-in Gram "
                         "matvec (default: direct kernel composition)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--verify", action="store_true",
                    help="assert score parity (1e-6) and fold-in parity "
                         "vs an explicit one-row solve (1e-4); nonzero "
                         "exit on failure")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write the load-generator report as JSON")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="enable obs tracing with a JSONL sink")
    return ap


def _gen_queries(rng, shape, n: int):
    import numpy as np
    return np.stack([rng.integers(0, s, size=n) for s in shape],
                    axis=1).astype(np.int32)


def _gen_histories(rng, shape, mode: int, users: int, nnz: int):
    import numpy as np
    others = [d for d in range(len(shape)) if d != mode]
    out = []
    for _ in range(users):
        oidx = np.stack([rng.integers(0, shape[d], size=nnz)
                         for d in others], axis=1).astype(np.int32)
        vals = rng.standard_normal(nnz).astype(np.float32)
        out.append((oidx, vals))
    return out


def _verify_scores(model, idx, scores) -> float:
    import numpy as np
    from repro.core.sparse_tensor import SparseTensor
    from repro.core.tttp import multilinear_values
    from repro.serve.model import apply_link

    st = SparseTensor.from_coo(idx, np.ones(idx.shape[0], np.float32),
                               model.shape)
    ref = apply_link(multilinear_values(st, model.factors), model.link)
    return float(np.abs(np.asarray(ref)[:idx.shape[0]] - scores).max())


def _verify_foldin(model, histories, mode, lam, rows) -> float:
    """Max |Δ| vs the explicit (Gram-forming) fresh one-row ALS solve."""
    import numpy as np

    err = 0.0
    others = [d for d in range(model.ndim) if d != mode]
    fs = [np.asarray(f) for f in model.factors]
    for u, (oidx, vals) in enumerate(histories):
        kr = fs[others[0]][oidx[:, 0]]
        for c, d in enumerate(others[1:], start=1):
            kr = kr * fs[d][oidx[:, c]]
        gram = kr.T @ kr + lam * np.eye(model.rank, dtype=kr.dtype)
        ref = np.linalg.solve(gram, kr.T @ vals)
        err = max(err, float(np.abs(rows[u] - ref).max()))
    return err


def main(argv=None) -> dict:
    """Run the CLI on ``argv`` (default ``sys.argv[1:]``) and return the
    report; exits with status 1 when ``--verify`` finds a mismatch."""
    args = build_parser().parse_args(argv)

    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()

    import jax
    import numpy as np

    from repro import obs
    from repro.serve import ServeEngine, load_factors, percentiles

    if args.trace:
        obs.enable(jsonl=args.trace)

    model = load_factors(args.factors, link=args.link, step=args.step)
    engine = ServeEngine(model, max_batch=args.batch_size,
                         topk_block=args.topk_block,
                         score_path=args.score_path,
                         foldin_lam=args.foldin_lam,
                         foldin_matvec_path=args.matvec_path)
    print(f"restored factors: shape={model.shape} rank={model.rank} "
          f"link={model.link} meta={ {k: model.meta[k] for k in sorted(model.meta) if k != 'shape'} }")
    report = {"shape": list(model.shape), "rank": model.rank,
              "link": model.link, "batch_size": args.batch_size}
    rng = np.random.default_rng(args.seed)
    failures = []

    # ---- entry-scoring load generator -----------------------------------
    queries = _gen_queries(rng, model.shape, args.num_queries)
    jax.block_until_ready(model.factors)       # exclude H2D from batch 0
    engine.score(queries[:args.batch_size])    # compile outside the clock
    lat = []
    scores = np.empty((args.num_queries,), np.float32)
    t_all = time.perf_counter()
    for lo in range(0, args.num_queries, args.batch_size):
        t0 = time.perf_counter()
        out = engine.score(queries[lo:lo + args.batch_size])
        lat.append(time.perf_counter() - t0)
        scores[lo:lo + out.shape[0]] = out
    wall = time.perf_counter() - t_all
    stats = percentiles(lat)
    stats["qps"] = args.num_queries / wall
    report["score"] = stats
    print(f"score: {args.num_queries} queries in {wall*1e3:.1f} ms -> "
          f"{stats['qps']:,.0f} QPS  p50={stats['p50_us']:.0f}us "
          f"p99={stats['p99_us']:.0f}us  (batch {args.batch_size})")

    if args.verify:
        err = _verify_scores(model, queries, scores)
        print(f"verify score parity vs multilinear_values: max|d|={err:.2e}")
        if err > 1e-6 * max(1.0, float(np.abs(scores).max())):
            failures.append(f"score parity {err:.3e} > 1e-6")

    # ---- top-k retrieval -------------------------------------------------
    if args.topk:
        fixed_modes = [d for d in range(model.ndim) if d != args.topk_mode]
        fixed = {d: rng.integers(0, model.shape[d], size=args.topk_users)
                 for d in fixed_modes}
        engine.top_k(fixed, args.topk_mode, args.topk)   # compile
        t0 = time.perf_counter()
        vals, idx = engine.top_k(fixed, args.topk_mode, args.topk)
        dt = time.perf_counter() - t0
        report["topk"] = {"k": args.topk, "users": args.topk_users,
                          "us_per_call": dt * 1e6}
        print(f"top-{args.topk} over mode {args.topk_mode} for "
              f"{args.topk_users} queries: {dt*1e3:.2f} ms/batch; "
              f"sample user0 -> items {idx[0, :5].tolist()} "
              f"scores {np.round(vals[0, :5], 3).tolist()}")

    # ---- cold-user fold-in ----------------------------------------------
    if args.foldin_users:
        hists = _gen_histories(rng, model.shape, args.foldin_mode,
                               args.foldin_users, args.foldin_nnz)
        engine.fold_in(hists, args.foldin_mode)   # compile
        t0 = time.perf_counter()
        rows = engine.fold_in(hists, args.foldin_mode)
        dt = time.perf_counter() - t0
        report["foldin"] = {"users": args.foldin_users,
                            "nnz": args.foldin_nnz,
                            "us_per_call": dt * 1e6}
        print(f"fold-in: {args.foldin_users} cold users x "
              f"{args.foldin_nnz} obs in {dt*1e3:.2f} ms "
              f"({dt*1e6/args.foldin_users:.0f} us/user)")
        if args.verify:
            err = _verify_foldin(model, hists, args.foldin_mode,
                                 args.foldin_lam, rows)
            print(f"verify fold-in vs explicit one-row ALS: "
                  f"max|d|={err:.2e}")
            if err > 1e-4:
                failures.append(f"fold-in parity {err:.3e} > 1e-4")

    if args.json:
        with open(args.json, "w") as f:
            json.dump(report, f, indent=2, sort_keys=True)
        print(f"wrote {args.json}")
    if failures:
        print("VERIFY FAILED: " + "; ".join(failures))
        sys.exit(1)
    if args.verify:
        print("verify OK")
    return report


if __name__ == "__main__":
    main()
