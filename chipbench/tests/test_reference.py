"""The plain reference against the program's ALS sweep at small size: the
program's sweeps pass the cells' limits, the reference in bfloat16 put in
the program's place (the control) fails them."""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import harness
import reference
from tiny import tiny_bench

SEEDS = (3, 4)


def _setup(tmp_path, workload, seed):
    bench = harness.load_benchmark(tiny_bench(tmp_path))
    spec = harness.resolve(workload, bench)
    devs = harness.devices_for(spec.cell["chips"], require_tpu=False)
    key = harness.seed_key(seed)
    idx, vals = spec.generator.generate(
        jax.random.fold_in(key, 0), spec.cfg,
        spec.cfg["nnz_per_chip"] * spec.cell["chips"])
    session = spec.solver.Session(spec.cfg, spec.traffic, devs, idx, vals,
                                  jax.random.fold_in(key, 1),
                                  jax.random.fold_in(key, 2))
    return spec, session, idx, vals


@pytest.mark.parametrize("workload", ["function.als"])
@pytest.mark.parametrize("seed", SEEDS)
def test_program_within_limits_and_control_outside(tmp_path, workload, seed):
    spec, session, idx, vals = _setup(tmp_path, workload, seed)
    cfg, traffic, lim = spec.cfg, spec.traffic, spec.limits
    args = (cfg["lam"], traffic["cg_tol"], traffic["cg_iters"],
            lim["settled_margin"])
    limit = lim["row_residual"]["limit"]
    f_in = jax.device_get(session.state0)
    for _ in range(2):
        f_out = jax.device_get(session.step(f_in))
        got = reference.check_sweep(idx, vals, f_in, f_out, *args)
        assert got["row_residual"] <= limit, got
        assert got["rows"] > 0.5 * got["rows_all"], got
        ctrl = reference.sweep(idx, vals, f_in, cfg["lam"], traffic["cg_tol"],
                               traffic["cg_iters"], jnp.bfloat16)
        ctrl = reference.check_sweep(
            idx, vals, f_in, [np.asarray(c, np.float32) for c in ctrl], *args)
        assert ctrl["row_residual"] > limit, ctrl
        f_in = f_out


def test_float32_reference_is_the_reference(tmp_path):
    """The reference's own float32 sweep reads as well as the program."""
    spec, session, idx, vals = _setup(tmp_path, "function.als", 3)
    cfg, traffic, lim = spec.cfg, spec.traffic, spec.limits
    f_in = jax.device_get(session.state0)
    out = reference.sweep(idx, vals, f_in, cfg["lam"], traffic["cg_tol"],
                          traffic["cg_iters"])
    got = reference.check_sweep(
        idx, vals, f_in, [np.asarray(c) for c in out], cfg["lam"],
        traffic["cg_tol"], traffic["cg_iters"], lim["settled_margin"])
    assert got["row_residual"] <= traffic["cg_tol"] * 1.01, got


def test_reference_shares_no_code_with_the_program():
    src = (harness.ROOT / "reference.py").read_text()
    assert "repro" not in src
    assert json.loads((harness.ROOT / "peaks.json").read_text())["source"]
