"""Smoke run of the completion trainer and the serving path on one TPU.

    python chip_smoke.py                # one chip: ALS, GGN, serving
    python chip_smoke.py --four-chips   # ALS under --mesh 4,1 and 2,2 vs LOCAL

Drives the user entry points in this one process (the only one that
touches JAX): ``repro.launch.complete.main`` and
``repro.launch.serve_complete.main``. The deployment is the paper's Netflix
tensor (``paper-netflix`` in ``launch/experiment.py``): extents 480,189 x
17,770 x 2,182, rank 32, lambda 1e-2, data from ``synthetic.netflix_like``
with a fixed seed. Only the number of nonzeros is cut (``NNZ_CUT``).

One chip:
1. ALS (quadratic loss), 3 sweeps; its factors are dumped for serving.
2. GGN (``poisson_log``), 2 sweeps.
3. Serving on ALS's factors: ``--verify`` scoring, top-k over the 17,770
   items, fold-in of cold users.

Checks, each of which makes the script exit non-zero: JAX's platform is
``tpu``; every requested sweep ran in this process; ALS's train RMSE is
finite and falls from the first sweep to the last; ALS's RMSE from the
chip's final factors on a fixed sample of 1M observed entries agrees with
a float64 NumPy recomputation to 1e-4 relative; ``serve_complete
--verify`` passes. With ``--four-chips`` the per-sweep RMSE of each mesh
run agrees with the LOCAL run to 1e-3 relative.

The last line of standard output is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import json
import math
import os
import statistics
import sys
import tempfile

ROOT = os.path.dirname(os.path.abspath(__file__))

NETFLIX_SHAPE = (480_189, 17_770, 2_182)
NETFLIX_NNZ = 100_477_727
RANK = 32
LAM = 1e-2
# Halved from the full 100,477,727 until both jitted sweeps compiled for a
# described v5e fit 16 GiB with 10% headroom: the XLA path keeps ~2.3 KiB
# of temporaries per nonzero (gathered rank-32 rows pad to 128 lanes), so
# 1/16 (6,279,857) needs 15.0 GiB and 1/32 needs 7.8 GiB (ALS), 6.8 (GGN).
NNZ_CUT = 32
NNZ = NETFLIX_NNZ // NNZ_CUT
ALS_SWEEPS = 3
GGN_SWEEPS = 2
GGN_DAMPING = 10.0        # launch/experiment.py's initial damping for *_log
SAMPLE = 1_000_000
SAMPLE_RTOL = 1e-4
MESH_RTOL = 1e-3
MESHES = ("4,1", "2,2")
FAMILIES = ("tttp", "mttkrp", "cg_matvec")


def complete_argv(ckpt_dir: str, algorithm: str, sweeps: int,
                  *extra: str) -> list:
    return ["--dataset", "netflix",
            "--dims", ",".join(str(d) for d in NETFLIX_SHAPE),
            "--nnz", str(NNZ), "--rank", str(RANK), "--lam", str(LAM),
            "--algorithm", algorithm, "--sweeps", str(sweeps),
            "--ckpt-dir", ckpt_dir, *extra]


class Smoke:
    def __init__(self, complete, obs, kops):
        self.complete = complete
        self.obs = obs
        self.kops = kops
        self.failures = []

    def check(self, ok: bool, what: str) -> None:
        print(f"  check {'ok  ' if ok else 'FAIL'} {what}")
        if not ok:
            self.failures.append(what)

    def train(self, tag: str, algorithm: str, sweeps: int, *extra: str):
        """One ``complete.main`` run with a fresh checkpoint directory;
        prints the kernel routes, dispatch fallback counters and timings."""
        self.obs.get_registry().reset()
        with tempfile.TemporaryDirectory() as ckpt:
            res = self.complete.main(complete_argv(ckpt, algorithm, sweeps,
                                                   *extra))
        hist = res["history"]
        routes = " ".join(f"{f}={self.kops.route(f)}" for f in FAMILIES)
        counters = {k: int(v) for k, v in sorted(
            self.obs.get_registry().summary()["counters"].items())}
        steady = [h[1] for h in hist[1:]] or [h[1] for h in hist]
        print(f"[{tag}] kernel routes: {routes}")
        print(f"[{tag}] trace-time dispatch fallback counters: {counters}")
        print(f"[{tag}] compile {res['compile_seconds']:.3f} s; "
              f"{len(hist)} sweeps; steady "
              f"{statistics.median(steady):.3f} s/sweep")
        self.check([h[0] for h in hist] == list(range(sweeps)),
                   f"{tag}: all {sweeps} sweeps ran in this process")
        self.check(all(math.isfinite(h[2]) for h in hist),
                   f"{tag}: train RMSE finite {[h[2] for h in hist]}")
        return res


def sample_rmse_check(smoke: Smoke, res) -> None:
    """ALS's RMSE on a fixed 1M-entry sample of Omega: the launcher's own
    ``train_rmse`` on the chip against float64 NumPy on the host."""
    import numpy as np
    from repro.core.sparse_tensor import SparseTensor

    st = res["tensor"]
    valid = np.asarray(st.valid)
    pos = np.flatnonzero(valid)
    pick = np.sort(np.random.default_rng(0).choice(
        pos, size=min(SAMPLE, pos.size), replace=False))
    idx = np.asarray(st.indices)[pick]
    vals = np.asarray(st.values)[pick]
    fs = res["factors"]
    chip = smoke.complete.train_rmse(
        SparseTensor.from_coo(idx, vals, st.shape), fs)
    prod = np.ones((idx.shape[0], RANK), np.float64)
    for d, f in enumerate(fs):
        prod *= np.asarray(f, np.float64)[idx[:, d]]
    host = float(np.sqrt(np.mean((vals.astype(np.float64)
                                  - prod.sum(axis=1)) ** 2)))
    rel = abs(chip - host) / host
    print(f"[als] sample RMSE on {idx.shape[0]} entries: chip {chip!r} "
          f"host float64 {host!r} rel {rel:.3e}")
    smoke.check(rel <= SAMPLE_RTOL,
                f"als: sample RMSE within {SAMPLE_RTOL} of float64 host")


def one_chip(smoke: Smoke, serve_complete) -> None:
    print(f"nnz {NNZ} = Netflix {NETFLIX_NNZ} / {NNZ_CUT} "
          f"(extents {NETFLIX_SHAPE}, rank {RANK}, lambda {LAM})")
    with tempfile.TemporaryDirectory() as work:
        dump = os.path.join(work, "als_factors")
        als = smoke.train("als", "als", ALS_SWEEPS, "--dump-factors", dump)
        hist = als["history"]
        smoke.check(hist[-1][2] < hist[0][2],
                    f"als: RMSE falls {hist[0][2]!r} -> {hist[-1][2]!r}")
        sample_rmse_check(smoke, als)
        del als

        smoke.train("ggn", "ggn", GGN_SWEEPS, "--loss", "poisson_log",
                    "--damping", str(GGN_DAMPING))

        smoke.obs.get_registry().reset()
        try:
            serve_complete.main(["--factors", dump, "--verify",
                                 "--num-queries", "65536",
                                 "--batch-size", "1024",
                                 "--topk", "10", "--topk-users", "32",
                                 "--foldin-users", "32",
                                 "--foldin-nnz", "64"])
            served = True
        except SystemExit as e:
            served = not e.code
        smoke.check(served, "serve: serve_complete --verify passed")


def four_chips(smoke: Smoke) -> None:
    print(f"nnz {NNZ} = Netflix {NETFLIX_NNZ} / {NNZ_CUT}; ALS "
          f"{ALS_SWEEPS} sweeps, LOCAL on device 0 vs --mesh {MESHES}")
    local = [h[2] for h in smoke.train("local", "als", ALS_SWEEPS)
             ["history"]]
    for mesh in MESHES:
        got = [h[2] for h in smoke.train(f"mesh {mesh}", "als", ALS_SWEEPS,
                                         "--mesh", mesh)["history"]]
        gap = max(abs(a - b) / abs(b) for a, b in zip(got, local))
        print(f"[mesh {mesh}] per-sweep RMSE {got} vs LOCAL {local}: "
              f"max rel gap {gap:.3e}")
        smoke.check(len(got) == len(local) and gap <= MESH_RTOL,
                    f"mesh {mesh}: per-sweep RMSE within {MESH_RTOL} of "
                    f"LOCAL")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only ALS under --mesh 4,1 and 2,2 against "
                         "LOCAL on four chips")
    args = ap.parse_args(argv)

    sys.path.insert(0, os.path.join(ROOT, "src"))
    try:
        from repro.launch.compile_cache import use_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the repro package is not beside this script "
              f"({e})", file=sys.stderr)
        return 1
    print(f"compile cache: {use_compile_cache()}")

    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX's platform is {platform!r} "
              f"({len(devices)} device(s)); there is no CPU mode",
              file=sys.stderr)
        return 1
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} chips, JAX sees {len(devices)}",
              file=sys.stderr)
        return 1
    print(f"device: {platform} {devices[0].device_kind} x{len(devices)}")

    from repro import obs
    from repro.kernels import ops as kops
    from repro.launch import complete, serve_complete
    obs.enable()
    smoke = Smoke(complete, obs, kops)
    for family in FAMILIES:
        print(f"route {family}: {kops.route(family)}"
              + (f" ({kops.TPU_REFUSED[family]})"
                 if family in kops.TPU_REFUSED else ""))
    if args.four_chips:
        four_chips(smoke)
    else:
        one_chip(smoke, serve_complete)
    if smoke.failures:
        print("chip_smoke FAILED: " + "; ".join(smoke.failures),
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
