"""End-to-end LOCAL-vs-mesh equivalence through the real CLI: every
algorithm launched via ``repro.launch.complete --mesh`` on 8 forced host
devices must produce factors matching the LOCAL run to 1e-4, with the
contractions dispatched through ``planner.execute`` (ISSUE 3 acceptance).

Subprocesses (one jax init each) because the forced-device XLA flag must be
set before jax initializes, and the main test process keeps the
single-device view per the harness contract."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

_DIMS = "24,20,16"
_NNZ = "4000"          # divisible by every data-shard count used below, so
                       # the ingest shuffle (keyed on padded cap) is identical
_COMMON = ["--dataset", "function", "--dims", _DIMS, "--nnz", _NNZ,
           "--sweeps", "2", "--cg-iters", "30", "--cg-tol", "1e-7"]

# (algorithm, mesh, rank): sgd keeps the data axis at size 1 — per-shard
# sampling decorrelates the RNG on >1 data shards by design, so its
# distributed run exercises the model (column-sharded) axis instead; the
# rank must divide the model axis.
CASES = [
    ("als", "4,2", "4"),
    ("ccd", "4,2", "4"),
    ("ccd_tttp", "4,2", "4"),
    ("sgd", "1,8", "8"),
    ("gcp", "4,2", "4"),
    ("ggn", "4,2", "4"),
]


def _run(tmp_path, tag, extra):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    dump = tmp_path / f"{tag}.npz"
    cmd = [sys.executable, "-m", "repro.launch.complete", *_COMMON, *extra,
           "--ckpt-dir", str(tmp_path / f"ckpt_{tag}"),
           "--dump-factors", str(dump)]
    out = subprocess.run(cmd, env=env, capture_output=True, text=True,
                         timeout=900,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stdout + "\n---\n" + out.stderr
    return np.load(dump)


@pytest.mark.slow
@pytest.mark.parametrize("algo,mesh,rank", CASES,
                         ids=[c[0] for c in CASES])
def test_mesh_run_matches_local(tmp_path, algo, mesh, rank):
    base = ["--algorithm", algo, "--rank", rank]
    local = _run(tmp_path, f"{algo}_local", base)
    dist = _run(tmp_path, f"{algo}_mesh",
                base + ["--mesh", mesh, "--force-host-devices", "8"])
    for k in local.files:
        np.testing.assert_allclose(dist[k], local[k], rtol=1e-4, atol=1e-4,
                                    err_msg=f"{algo} factor {k}")


def test_main_in_process_runs_every_sweep(tmp_path):
    """``main(argv)`` is callable in-process (chip_smoke.py drives it that
    way) and reports every sweep it ran."""
    from repro.launch import complete

    res = complete.main(["--dataset", "netflix", "--dims", "30,20,10",
                         "--nnz", "800", "--rank", "4", "--sweeps", "3",
                         "--lam", "1e-2", "--ckpt-dir", str(tmp_path)])
    assert [h[0] for h in res["history"]] == [0, 1, 2]
    assert res["history"][-1][2] < res["history"][0][2]
    assert res["compile_seconds"] > 0
    assert complete.train_rmse(res["tensor"], res["factors"]) == \
        pytest.approx(res["history"][-1][2], rel=1e-6)


def test_als_sweep_line_names_each_modes_cg_steps(tmp_path, capsys):
    """An ALS sweep's line carries the CG steps each mode ran."""
    from repro.launch import complete

    complete.main(["--dataset", "function", "--dims", "30,20,10",
                   "--nnz", "800", "--rank", "4", "--sweeps", "2",
                   "--cg-iters", "7", "--ckpt-dir", str(tmp_path)])
    lines = [ln for ln in capsys.readouterr().out.splitlines()
             if ln.startswith("sweep ")]
    assert len(lines) == 2
    for ln in lines:
        steps = json.loads(ln.split("cg_steps=")[1])
        assert len(steps) == 3 and all(0 <= n <= 7 for n in steps), ln


@pytest.mark.parametrize("env", [None, "elsewhere"])
def test_compile_cache_dir(monkeypatch, tmp_path, env):
    import jax
    from repro.launch import compile_cache

    before = jax.config.jax_compilation_cache_dir
    if env is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = str(compile_cache.CHECKOUT_CACHE_DIR)
    else:
        want = str(tmp_path / env)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", want)
    try:
        assert compile_cache.use_compile_cache() == want
        if env is None:
            assert jax.config.jax_compilation_cache_dir == want
            assert compile_cache.CHECKOUT_CACHE_DIR.parent == \
                Path(__file__).resolve().parents[1]
        else:
            assert jax.config.jax_compilation_cache_dir == before
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
