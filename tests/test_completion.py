"""Integration tests: the three completion algorithms converge on a low-rank
synthetic tensor (paper Fig. 7a protocol, laptop scale), generalized losses
descend, and the two CCD++ variants agree exactly."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import losses as L
from repro.core.completion import (als_sweep, als_sweep_explicit,
                                   als_sweep_stats, ccd_sweep,
                                   ccd_sweep_tttp, gcp_adam_init, gcp_step,
                                   sgd_sweep)
from repro.core.completion.ccd import residual_values
from repro.core.completion.gcp import gcp_loss
from repro.core.sparse_tensor import SparseTensor
from repro.core.tttp import multilinear_values


def make_problem(key, shape=(40, 35, 30), r_true=3, r=6, nnz=4000):
    ks = jax.random.split(key, 8)
    true = [jax.random.normal(k, (d, r_true)) / r_true ** 0.5
            for k, d in zip(ks, shape)]
    idx = jnp.stack([jax.random.randint(ks[3 + d], (nnz,), 0, s)
                     for d, s in enumerate(shape)], 1)
    vals = jnp.sum(true[0][idx[:, 0]] * true[1][idx[:, 1]] *
                   true[2][idx[:, 2]], 1)
    st = SparseTensor.from_coo(idx, vals, shape, cap=nnz + 96)
    init = [jax.random.normal(jax.random.fold_in(ks[6], d), (s, r)) / r ** 0.5
            for d, s in enumerate(shape)]
    return st, init


def rmse(st, fs):
    model = multilinear_values(st, fs)
    d = (st.values - model) * st.mask
    return float(jnp.sqrt(jnp.sum(d ** 2) / jnp.sum(st.mask)))


def test_als_cg_converges_and_matches_explicit():
    st, fs = make_problem(jax.random.PRNGKey(0))
    omega = st.with_values(jnp.ones_like(st.values))
    e0 = rmse(st, fs)
    sweep = jax.jit(lambda s, o, a, b, c: als_sweep(s, o, [a, b, c], 1e-6,
                                                    cg_iters=16))
    f_cg = list(fs)
    for _ in range(25):
        f_cg = sweep(st, omega, *f_cg)
    assert rmse(st, f_cg) < 0.1 * e0
    # one sweep from same init agrees with the explicit (Cholesky) baseline
    f1 = sweep(st, omega, *fs)
    f2 = jax.jit(lambda s, a, b, c: als_sweep_explicit(s, [a, b, c], 1e-6))(
        st, *fs)
    for a, b in zip(f1, f2):
        np.testing.assert_allclose(a, b, rtol=3e-2, atol=3e-2)


def test_als_sweep_stats_counts_cg_steps_and_matches_als_sweep():
    """``als_sweep_stats`` returns ``als_sweep``'s factors bit for bit, and
    each mode's CG steps: the step bound where it binds, fewer where the
    tolerance stops CG first."""
    st, fs = make_problem(jax.random.PRNGKey(2))
    omega = st.with_values(jnp.ones_like(st.values))

    def both(tol, iters):
        plain = jax.jit(lambda s, o, f: als_sweep(
            s, o, list(f), 1e-4, cg_tol=tol, cg_iters=iters))(st, omega, fs)
        stats, steps = jax.jit(lambda s, o, f: als_sweep_stats(
            s, o, list(f), 1e-4, cg_tol=tol, cg_iters=iters))(st, omega, fs)
        for a, b in zip(plain, stats):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert steps.dtype == jnp.int32 and steps.shape == (st.ndim,)
        return np.asarray(steps).tolist()

    assert both(1e-12, 3) == [3, 3, 3]
    steps = both(1e-3, 30)
    assert all(1 <= n < 30 for n in steps), steps


def test_ccd_variants_identical_and_converge():
    st, fs = make_problem(jax.random.PRNGKey(1))
    rho = residual_values(st, fs)
    e0 = rmse(st, fs)
    s1 = jax.jit(lambda s, f, r: ccd_sweep(s, f, r, 1e-6))
    s2 = jax.jit(lambda s, f, r: ccd_sweep_tttp(s, f, r, 1e-6))
    fa, ra = list(fs), rho
    fb, rb = list(fs), rho
    for _ in range(8):
        fa, ra = s1(st, fa, ra)
        fb, rb = s2(st, fb, rb)
    for a, b in zip(fa, fb):
        np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)
    assert rmse(st, fa) < 0.5 * e0
    # maintained residual stays consistent with direct recomputation
    np.testing.assert_allclose(ra, residual_values(st, fa),
                               rtol=1e-3, atol=1e-3)


def test_sgd_descends():
    st, fs = make_problem(jax.random.PRNGKey(2))
    e0 = rmse(st, fs)
    step = jax.jit(lambda k, s, f: sgd_sweep(k, s, f, 1e-6, lr=4e-3,
                                             sample_size=2048))
    key = jax.random.PRNGKey(3)
    for i in range(100):
        fs = step(jax.random.fold_in(key, i), st, fs)
    assert rmse(st, fs) < 0.75 * e0


@pytest.mark.parametrize("loss_name", ["quadratic", "poisson", "poisson_log",
                                       "huber", "logistic"])
def test_gcp_generalized_losses_descend(loss_name):
    st, fs = make_problem(jax.random.PRNGKey(4))
    loss = L.LOSSES[loss_name]
    if loss_name.startswith("poisson"):
        st = st.with_values(jnp.round(jnp.abs(st.values) * 4))
        fs = [jnp.abs(f) + 0.05 for f in fs]
    if loss_name == "logistic":
        st = st.with_values((st.values > 0).astype(jnp.float32))
    ad = gcp_adam_init(fs)
    step = jax.jit(lambda s, f, a: gcp_step(s, f, loss, 1e-7, 5e-3, a))
    l0 = float(gcp_loss(st, fs, loss, 1e-7))
    for _ in range(60):
        fs, ad = step(st, fs, ad)
    l1 = float(gcp_loss(st, fs, loss, 1e-7))
    assert l1 < l0, (loss_name, l0, l1)


def test_ccd_tttp_variant_uses_two_tttp_calls_per_column(monkeypatch):
    """Perf regression guard: the TTTP-routed column update reuses
    vw = TTTP(Ω, fac) for both the numerator and the residual update —
    two TTTP kernel calls per column update, not three — and stays
    numerically identical to the einsum variant."""
    import repro.planner as planner_mod
    from repro.core.completion.ccd import (_ccd_column_update_einsum,
                                           _ccd_column_update_tttp,
                                           residual_values)
    from repro.core.distributed import LOCAL
    st, fs = make_problem(jax.random.PRNGKey(7), nnz=600)
    rho = residual_values(st, fs)
    cols = [f[:, 0] for f in fs]
    calls = []
    orig = planner_mod.planned_tttp

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(planner_mod, "planned_tttp", counting)
    col_t, rho_t = _ccd_column_update_tttp(rho, st, cols, 0, 1e-6, LOCAL)
    assert len(calls) == 2, f"expected 2 TTTP calls, got {len(calls)}"
    col_e, rho_e = _ccd_column_update_einsum(rho, st, cols, 0, 1e-6, LOCAL)
    np.testing.assert_allclose(col_t, col_e, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(rho_t, rho_e, rtol=1e-5, atol=1e-5)


def test_sgd_sample_entries_empty_shard():
    """Regression: a shard with zero valid entries must not feed an all-zero
    probability vector to jax.random.choice (garbage indices / NaNs under
    sharded SGD). The fallback samples uniformly, marks the sample invalid,
    and the sweep stays finite."""
    from repro.core.completion.sgd import sample_entries
    shape = (10, 8, 6)
    cap = 32
    empty = SparseTensor(jnp.zeros((cap, 3), jnp.int32), jnp.zeros((cap,)),
                         jnp.zeros((cap,), bool), shape)
    s = sample_entries(jax.random.PRNGKey(0), empty, 16)
    idx = np.asarray(s.indices)
    assert np.all(np.isfinite(idx))
    assert np.all(idx >= 0) and all(
        np.all(idx[:, d] < shape[d]) for d in range(3))
    assert not bool(jnp.any(s.valid))
    # a full sgd sweep on the empty shard: finite, regularization-only drift
    fs = [jax.random.normal(jax.random.PRNGKey(d), (n, 4))
          for d, n in enumerate(shape)]
    out = sgd_sweep(jax.random.PRNGKey(1), empty, list(fs), lam=1e-3,
                    lr=1e-2, sample_size=16)
    for f in out:
        assert bool(jnp.all(jnp.isfinite(f)))
    # under jit as well (the sharded code path always traces)
    out_j = jax.jit(lambda k, s_, f: sgd_sweep(k, s_, list(f), 1e-3, 1e-2,
                                               16))(jax.random.PRNGKey(1),
                                                    empty, tuple(fs))
    for f in out_j:
        assert bool(jnp.all(jnp.isfinite(f)))


def test_gcp_quadratic_grad_matches_autodiff():
    """MTTKRP-based GCP gradient == jax.grad of the objective."""
    from repro.core.completion.gcp import gcp_gradients
    st, fs = make_problem(jax.random.PRNGKey(5), nnz=500)
    lam = 1e-3

    def objective(factors):
        model = multilinear_values(st, factors)
        data = jnp.sum(jnp.where(st.mask,
                                 L.quadratic.value(st.values, model), 0.0))
        return data + lam * sum(jnp.sum(jnp.square(f)) for f in factors)

    got = gcp_gradients(st, fs, L.quadratic, lam)
    want = jax.grad(objective)(fs)
    for g, w in zip(got, want):
        np.testing.assert_allclose(g, w, rtol=1e-4, atol=1e-4)
