"""Generalized Gauss-Newton solver + planner cg_matvec family tests:
the weighted eq.-3 Gram matvec agrees with the dense reference on EVERY
planner path, the fused kernel is reachable from dispatch, PCG solves SPD
systems, and GGN converges (quadratic: beats the ALS 10-sweep RMSE in ≤ 5
iterations on the synthetic function tensor; generalized losses descend)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import planner
from repro.core import losses as L
from repro.core.completion import als_sweep, batched_pcg, ggn_init, ggn_sweep
from repro.core.completion.als import gram_matvec
from repro.core.completion.gauss_newton import (curvature_tensor,
                                                ggn_update_mode,
                                                joint_ggn_matvec)
from repro.core.completion.gcp import gcp_loss
from repro.core.sparse_tensor import SparseTensor
from repro.core.tttp import multilinear_values


def _problem(key, shape=(13, 11, 7), nnz=60, r=4):
    st = SparseTensor.random(key, shape, nnz, cap=nnz + 6)
    ks = jax.random.split(key, len(shape) + 1)
    fs = [jax.random.normal(k, (d, r)) for k, d in zip(ks, shape)]
    return st, fs


def _dense_gram_matvec(w, fs, mode, x):
    """Dense reference: y[i,r] = Σ_n ω_n kr_{n,r} Σ_s kr_{n,s} x[i_n,s]."""
    nd = w.ndim
    letters = "ijk"
    others = [d for d in range(nd) if d != mode]
    s_terms = [letters[d] + "s" for d in others] + [letters[mode] + "s"]
    r_terms = [letters[d] + "r" for d in others]
    expr = ("ijk," + ",".join(s_terms + r_terms) + "->" + letters[mode] + "r")
    ops = [w] + [fs[d] for d in others] + [x] + [fs[d] for d in others]
    return jnp.einsum(expr, *ops)


@pytest.mark.parametrize("mode", [0, 1, 2])
def test_weighted_gram_matvec_every_path_matches_dense(mode):
    """Acceptance: every planner path of the weighted Gram matvec (fused
    cg_matvec_bucketed, TTTP+MTTKRP, H-sliced, dense) agrees with the dense
    reference to 1e-4 — with NON-uniform curvature weights."""
    key = jax.random.PRNGKey(0)
    st, fs = _problem(key)
    w_st = st.with_values(jnp.abs(st.values) + 0.3)   # ω > 0, non-uniform
    x = jax.random.normal(jax.random.fold_in(key, 5), fs[mode].shape)
    want = _dense_gram_matvec(w_st.todense(), fs, mode, x)
    plan = planner.plan_contraction(
        "abc,bz,cz,ay,by,cy->az" if mode == 0 else
        ("abc,az,cz,by,ay,cy->bz" if mode == 1 else "abc,az,bz,cy,ay,by->cz"),
        tuple([w_st] + [fs[d] for d in range(3) if d != mode] + [x] +
              [fs[d] for d in range(3) if d != mode]))
    assert plan.ir.kind == "cg_matvec"
    assert set(plan.candidates) == {"fused", "tttp_mttkrp", "sliced", "dense"}
    for path in plan.candidates:
        got = planner.planned_cg_matvec(w_st, fs, mode, x, path=path)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=f"mode {mode} via {path}")
    # cost-model default agrees too
    got = planner.planned_cg_matvec(w_st, fs, mode, x)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


def test_gram_matvec_matvec_path_routes_and_agrees():
    """als.gram_matvec(matvec_path=...) == the direct composition (+λx),
    for every path and under jit (where fused falls back safely)."""
    key = jax.random.PRNGKey(1)
    st, fs = _problem(key)
    w_st = st.with_values(jnp.abs(st.values) + 0.1)
    x = jax.random.normal(jax.random.fold_in(key, 2), fs[0].shape)
    lam = 0.37
    want = gram_matvec(w_st, fs, 0, x, lam=lam)
    for path in ("fused", "tttp_mttkrp", "sliced", "dense", "auto"):
        got = gram_matvec(w_st, fs, 0, x, lam=lam, matvec_path=path)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4,
                                   err_msg=path)
        jitted = jax.jit(lambda w, a, b, c, xx: gram_matvec(
            w, [a, b, c], 0, xx, lam=lam, matvec_path=path))
        np.testing.assert_allclose(jitted(w_st, *fs, x), want,
                                   rtol=1e-4, atol=1e-4, err_msg=path)


def test_fused_path_reaches_cg_matvec_bucketed(monkeypatch):
    """The fused planner path actually lowers onto the previously-unreachable
    kernels.ops.cg_matvec_bucketed (eager dispatch only)."""
    from repro.kernels import ops as kops
    calls = []
    orig = kops.cg_matvec_bucketed
    monkeypatch.setattr(kops, "cg_matvec_bucketed",
                        lambda *a, **k: calls.append(1) or orig(*a, **k))
    key = jax.random.PRNGKey(2)
    st, fs = _problem(key)
    w_st = st.with_values(jnp.ones_like(st.values))
    x = jax.random.normal(key, fs[1].shape)
    planner.planned_cg_matvec(w_st, fs, 1, x, path="fused")
    assert calls, "fused path did not dispatch to cg_matvec_bucketed"


def test_joint_ggn_matvec_matches_dense():
    """The joint GGN matvec covers all N² Jacobian blocks: compare against
    an explicitly assembled dense H = JᵀWJ + shift·I."""
    key = jax.random.PRNGKey(3)
    shape, r = (7, 6, 5), 3
    st, fs = _problem(key, shape=shape, nnz=40, r=r)
    loss = L.quadratic
    w_st, _ = curvature_tensor(st, fs, loss)
    xs = [jax.random.normal(jax.random.fold_in(key, d), f.shape)
          for d, f in enumerate(fs)]
    shift = 0.21
    got = joint_ggn_matvec(st, w_st, fs, xs, shift)
    # dense reference: J columns indexed by (mode, row, r)
    mask = np.asarray(st.mask)
    idx = np.asarray(st.indices)[mask]
    w = np.asarray(w_st.values)[np.asarray(st.mask)]
    f_np = [np.asarray(f) for f in fs]
    m = idx.shape[0]
    cols = []
    for d in range(3):
        jd = np.zeros((m, shape[d], r))
        kr = np.ones((m, r))
        for e in range(3):
            if e != d:
                kr = kr * f_np[e][idx[:, e]]
        for n in range(m):
            jd[n, idx[n, d], :] = kr[n]
        cols.append(jd.reshape(m, -1))
    J = np.concatenate(cols, axis=1)
    H = J.T @ (w[:, None] * J) + shift * np.eye(J.shape[1])
    xflat = np.concatenate([np.asarray(x).ravel() for x in xs])
    want = H @ xflat
    got_flat = np.concatenate([np.asarray(g).ravel() for g in got])
    np.testing.assert_allclose(got_flat, want, rtol=1e-4, atol=1e-4)


def test_batched_pcg_solves_spd_with_preconditioner():
    key = jax.random.PRNGKey(4)
    n, r = 20, 6
    a = jax.random.normal(key, (n, r, r))
    spd = jnp.einsum("nij,nkj->nik", a, a) + 0.3 * jnp.eye(r)[None]
    b = jax.random.normal(jax.random.fold_in(key, 1), (n, r))
    mv = lambda x: jnp.einsum("nij,nj->ni", spd, x)
    diag = jnp.stack([jnp.diag(spd[i]) for i in range(n)])
    x, iters = batched_pcg(mv, b, jnp.zeros_like(b),
                           precond=lambda v: v / diag,
                           tol=1e-6, max_iters=4 * r + 10)
    np.testing.assert_allclose(mv(x), b, rtol=2e-3, atol=2e-3)
    # no preconditioner reduces to plain CG
    x2, _ = batched_pcg(mv, b, jnp.zeros_like(b), tol=1e-6,
                        max_iters=4 * r + 10)
    np.testing.assert_allclose(mv(x2), b, rtol=2e-3, atol=2e-3)


def test_ggn_update_mode_matches_als_for_quadratic():
    """For quadratic loss and μ→0, one per-mode GGN update equals the ALS
    implicit-CG update (same normal equations)."""
    from repro.core.completion.als import als_update_mode
    key = jax.random.PRNGKey(5)
    shape = (15, 12, 10)
    st, fs = _problem(key, shape=shape, nnz=300, r=4)
    omega = st.with_values(jnp.ones_like(st.values))
    lam = 1e-4
    want, _ = als_update_mode(st, omega, list(fs), 0, lam, cg_tol=1e-8,
                              cg_iters=60)
    got = ggn_update_mode(st, list(fs), 0, L.quadratic, lam, damping=0.0,
                          cg_tol=1e-8, cg_iters=60)
    np.testing.assert_allclose(got, want, rtol=2e-3, atol=2e-3)


def _function_problem(seed=0, shape=(80, 70, 60), nnz=40_000, r=8):
    from repro.data import synthetic
    key = jax.random.PRNGKey(seed)
    st = synthetic.function_tensor(key, shape, nnz)
    ks = jax.random.split(key, len(shape))
    fs = [jax.random.normal(k, (d, r)) / r ** 0.5
          for k, d in zip(ks, shape)]
    return st, fs


def _rmse(st, fs):
    model = multilinear_values(st, fs)
    d = (st.values - model) * st.mask
    return float(jnp.sqrt(jnp.sum(d ** 2) / jnp.sum(st.mask)))


def test_ggn_quadratic_reaches_als_10sweep_rmse_in_5_iters():
    """Acceptance: on the synthetic function tensor, GGN with quadratic
    loss reaches the RMSE of 10 ALS sweeps in ≤ 5 GGN iterations (the
    joint LM step + per-mode pass captures cross-mode curvature that
    block-coordinate ALS cannot)."""
    st, fs = _function_problem()
    lam = 1e-5
    omega = st.with_values(jnp.ones_like(st.values))
    als = jax.jit(lambda s, o, f: tuple(als_sweep(s, o, list(f), lam,
                                                  cg_iters=20)))
    f_als = tuple(fs)
    for _ in range(10):
        f_als = als(st, omega, f_als)
    als10 = _rmse(st, list(f_als))

    ggn = jax.jit(lambda s, stt: ggn_sweep(s, stt, L.quadratic, lam,
                                           cg_iters=20))
    state = ggn_init(fs)
    best = np.inf
    for _ in range(5):
        state = ggn(st, state)
        best = min(best, _rmse(st, list(state.factors)))
    assert best <= als10, (best, als10)


@pytest.mark.parametrize("loss_name", ["poisson_log", "logistic", "huber"])
def test_ggn_descends_generalized_losses(loss_name):
    """GGN decreases the generalized objective (second-order counterpart of
    the first-order GCP path) and never increases it (LM acceptance)."""
    st, fs = _problem(jax.random.PRNGKey(6), shape=(25, 20, 15), nnz=900,
                      r=4)
    loss = L.LOSSES[loss_name]
    if loss_name.startswith("poisson"):
        st = st.with_values(jnp.round(jnp.abs(st.values) * 4))
    if loss_name == "logistic":
        st = st.with_values((st.values > 0).astype(jnp.float32))
    fs = [0.3 * f for f in fs]
    lam = 1e-6
    step = jax.jit(lambda s, stt: ggn_sweep(s, stt, loss, lam, cg_iters=12,
                                            joint_iters=8, precond_iters=4))
    state = ggn_init(fs, damping=1e-3)
    hist = [float(gcp_loss(st, list(state.factors), loss, lam))]
    for _ in range(4):
        state = step(st, state)
        hist.append(float(gcp_loss(st, list(state.factors), loss, lam)))
    assert hist[-1] < hist[0], hist
    assert all(b <= a + 1e-5 for a, b in zip(hist, hist[1:])), hist


def test_ggn_poisson_curvature_weights_clamp():
    """Below the poisson floor the curvature weight is exactly 0 (the
    clamped hess), keeping the GGN system PSD."""
    st, fs = _problem(jax.random.PRNGKey(7))
    st = st.with_values(jnp.round(jnp.abs(st.values) * 3))
    fs = [-jnp.abs(f) for f in fs]      # drive the model negative
    w_st, model = curvature_tensor(st, fs, L.poisson)
    assert bool(jnp.all(w_st.values[model < L._EPS * 0.99] == 0.0))
    assert bool(jnp.all(w_st.values >= 0.0))
