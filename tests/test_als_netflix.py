"""ALS on a small Netflix-shaped tensor against the benchmark's plain
reference (``chipbench/reference.py``, which shares no code with the
program).

The tensor comes from the benchmark's own generator
(``chipbench/generators/fitted_counts.py``) with the ``netflix``
configuration's published per-user and per-movie counts, cut to 4,000
users, 120 movies and 40 days at 209 ratings a user in the whole data
set, of which 16,000 are drawn: about 4 ratings a user, as on one chip of
the deployment, so the user mode keeps the COO Gram matvec, while the
movie and day modes hold 133 and 400 ratings a row and take the row-slab
operator, the longest movie rows spanning a dozen slabs. Rank, lambda and
the CG settings are the cell's (``netflix.als``)."""
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.core.completion import als, als_sweep_stats
from repro.core.sparse_tensor import SparseTensor

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_BENCH = os.path.join(_ROOT, "chipbench")
SHAPE = (4000, 120, 40)
NNZ = 16000


def _load(name, *path):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(_BENCH, *path))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*path):
    with open(os.path.join(_BENCH, *path)) as f:
        return json.load(f)


reference = _load("chipbench_reference", "reference.py")
fitted_counts = _load("chipbench_fitted_counts", "generators",
                      "fitted_counts.py")
CFG = _json("configs", "netflix.json")
TRAFFIC = _json("traffic", "als.json")
LIMITS = _json("limits", "netflix.als.json")


def _problem(seed):
    """The small tensor, its all-ones Ω and seeded factors normal/√R."""
    cfg = dict(CFG, shape=list(SHAPE), nnz_total=SHAPE[0] * 209)
    key = jax.random.PRNGKey(seed)
    idx, vals = fitted_counts.generate(key, cfg, NNZ)
    st = SparseTensor.from_coo(idx, vals, SHAPE)
    omega = st.with_values(jnp.ones_like(st.values))
    r = CFG["rank"]
    ks = jax.random.split(jax.random.fold_in(key, 1), len(SHAPE))
    fs = tuple(jax.random.normal(k, (d, r)) / r ** 0.5
               for k, d in zip(ks, SHAPE))
    return idx, vals, st, omega, fs


def _sweep():
    lam, tol, iters = CFG["lam"], TRAFFIC["cg_tol"], TRAFFIC["cg_iters"]
    return jax.jit(lambda s, o, fs: (lambda f, n: (tuple(f), n))(
        *als_sweep_stats(s, o, list(fs), lam, cg_tol=tol, cg_iters=iters)))


def test_user_mode_coo_and_long_modes_slab():
    """The shape rule puts every mode on the slab operator: the short user
    rows on slabs of 8 slots, the movie and day rows on slabs of 128; the
    gauges say how each mode was laid out, and tracing with ``obs`` off
    records none. (The name is the one this test had when the user mode
    kept the COO matvec.)"""
    _, _, st, omega, fs = _problem(3)
    assert all(als.slab_mode(omega, d) for d in range(3))
    assert [als.slab_width(omega, d) for d in range(3)] == [8, 128, 128]
    obs.get_registry().reset()
    obs.enable()
    try:
        _sweep().lower(st, omega, fs)
        summary = obs.get_registry().summary()
    finally:
        obs.disable()
        obs.get_registry().reset()
    counters = {k: v for k, v in summary["counters"].items()
                if k.startswith("als/gram/")}
    assert counters == {"als/gram/slab": 3.0}
    g = summary["gauges"]
    assert g["als/gram/mode_0/cap"] == st.cap
    assert g["als/gram/mode_0/slots"] >= st.cap + SHAPE[0] * (8 - 1)
    for d, width in enumerate((8, 128, 128)):
        assert g[f"als/gram/mode_{d}/width"] == width
        assert g[f"als/gram/mode_{d}/slots"] >= st.cap
        assert f"als/gram/mode_{d}/lanes" not in g
    _sweep().lower(st, omega, fs)
    assert not obs.get_registry().summary()["gauges"]


@pytest.mark.parametrize("seed", [3, 4])
def test_mixed_sweep_matches_reference(seed):
    """Three sweeps from seeded factors, each redone mode by mode by the
    reference from the same inputs: every row the reference settles with
    the cell's margin has a residual within the cell's limit under the
    reference's own normal equations, those rows are most rows with data
    (93 to 96% at this shape, 84% at the cell's), and each mode runs
    within one CG step of the reference. The
    reference in bfloat16 (the cell's control) misses the same limit, so
    the comparison can tell the precisions apart at this shape."""
    idx, vals, st, omega, fs = _problem(seed)
    lam, tol, iters = CFG["lam"], TRAFFIC["cg_tol"], TRAFFIC["cg_iters"]
    margin, limit = LIMITS["settled_margin"], LIMITS["row_residual"]["limit"]
    sweep = _sweep()
    f_in = jax.device_get(fs)
    for _ in range(3):
        f_out, steps = jax.device_get(sweep(st, omega, f_in))
        got = reference.check_sweep(idx, vals, f_in, f_out, lam, tol, iters,
                                    margin)
        assert got["row_residual"] <= limit, got
        assert got["rows"] >= 0.9 * got["rows_all"], got
        assert np.all(np.abs(np.asarray(steps) - got["cg_steps"]) <= 1), (
            steps, got["cg_steps"])
        f_in = f_out
    ctrl = reference.sweep(idx, vals, f_in, lam, tol, iters, jnp.bfloat16)
    ctrl = reference.check_sweep(
        idx, vals, f_in, [np.asarray(c, np.float32) for c in ctrl], lam, tol,
        iters, margin)
    assert ctrl["row_residual"] > limit, ctrl
