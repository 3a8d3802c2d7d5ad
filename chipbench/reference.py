"""Plain ALS for CP tensor completion in ``jax.numpy``, independent of the
program under test.

One ALS sweep updates each mode in turn. Row ``i`` of mode ``d`` solves
``(G_i + lam I) x_i = b_i`` with ``G_i = sum_n k_n k_n^T`` and
``b_i = sum_n t_n k_n`` over the observed entries ``n`` in that row, where
``k_n`` is the elementwise product of the other modes' factor rows. The
solve is batched conjugate gradient started from the row's previous value,
with the semantics the configurations state: a row stops when its residual
norm falls to ``cg_tol`` times ``|b_i|`` (``|b_i|`` floored at 1e-15), and
no row takes more than ``cg_iters`` steps.

The program never forms ``G_i``; this reference does, in blocks of
nonzeros, and applies it row by row with elementwise products and sums. The two agree in exact
arithmetic and round apart. ``dtype`` is the precision in which every
array that the sweep gathers per nonzero or keeps per row is held: the
data, the factor rows and their products, the CG iterate and its search
direction. Sums (``G``, ``b``, the residual, inner products) accumulate in
float32; no step is a matrix product, so the TPU rounds no float32
operand to bfloat16. With ``dtype`` bfloat16 this is the
control: the reference at the precision below the one the configurations
state, as a sweep that gathered bfloat16 rows would compute it.
"""
from __future__ import annotations

import functools
from typing import List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BLOCK_BYTES = 1 << 28       # bytes of k_n k_n^T outer products per block
MARGINS = (0, 1, 2, 3, 4, 6, 8, 10)  # settling margins for the record


def _arith(dtype):
    """``(arithmetic dtype, rounder)``: arithmetic in float32 (float64 for
    a float64 reference), and a function that rounds a stored array to
    ``dtype``, as a chip that keeps ``dtype`` in memory and accumulates in
    float32 does."""
    acc = jnp.promote_types(dtype, jnp.float32)
    if acc == jnp.dtype(dtype):
        return acc, lambda v: v
    return acc, lambda v: v.astype(dtype).astype(acc)


@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6))
def _normal_equations(idx, vals, others, mode, n_rows, block, dtype):
    """``b (R, n_rows)``, ``G (R, R, n_rows)`` and ``T (n_rows,)``, the sum
    of the squared values in each row, summed over blocks of ``block``
    nonzeros. ``others`` holds ``None`` at ``mode``. Rows run along the
    last axis, so that no array pads a short axis out to the TPU's tile."""
    acc, rnd = _arith(dtype)
    others = [None if f is None else rnd(f.astype(acc)) for f in others]
    m = idx.shape[0]
    r = next(f.shape[1] for f in others if f is not None)
    n_blocks = -(-m // block)
    pad = n_blocks * block - m
    idx = jnp.pad(idx, ((0, pad), (0, 0)))
    vals = jnp.pad(rnd(vals.astype(acc)), (0, pad))
    live = jnp.pad(jnp.ones((m,), acc), (0, pad))

    def body(c, sums):
        b, g, t2 = sums
        sl = lambda a: jax.lax.dynamic_slice_in_dim(a, c * block, block)
        ix, tv, w = sl(idx), sl(vals), sl(live)
        k = None
        for e, f in enumerate(others):
            if f is None:
                continue
            rows = f[ix[:, e]]
            k = rows if k is None else rnd(k * rows)
        k = w[:, None] * k
        rows = ix[:, mode]
        b = b.at[rows].add(tv[:, None] * k)
        g = g.at[rows].add((k[:, :, None] * k[:, None, :]).reshape(-1, r * r))
        t2 = t2.at[rows].add(w * tv * tv)
        return b, g, t2

    init = (jnp.zeros((n_rows, r), acc), jnp.zeros((n_rows, r * r), acc),
            jnp.zeros((n_rows,), acc))
    b, g, t2 = jax.lax.fori_loop(0, n_blocks, body, init)
    return b.T, g.T.reshape(r, r, n_rows), t2


def _matvec(g, lam, p):
    """``(G_i + lam I) p_i`` for every row ``i``; ``p`` is ``(R, rows)``."""
    return jnp.sum(g * p[None, :, :], axis=1) + lam * p


@functools.partial(jax.jit, static_argnums=(5, 6))
def _batched_cg(g, b, x0, lam, tol, iters, dtype):
    """Batched CG on the rows' systems from ``x0 (rows, R)``. Returns the
    answer ``(rows, R)``, the steps taken and, per row, the step at which
    it reached ``tol`` (-1 where it never did)."""
    acc, rnd = _arith(dtype)
    g, b, x0 = g.astype(acc), b.astype(acc), rnd(x0.T.astype(acc))
    lam = jnp.asarray(lam, acc)
    matvec = functools.partial(_matvec, g, lam)

    def rowdot(u, v):
        return jnp.sum(u * v, axis=0)

    thresh = jnp.asarray(tol, acc) ** 2 * jnp.maximum(
        rowdot(b, b), jnp.asarray(1e-30, acc))
    r = b - matvec(x0)
    rs = rowdot(r, r)
    done0 = jnp.where(rs > thresh, -1, 0)

    def cond(s):
        i, _, _, _, rs, _ = s
        return (i < iters) & jnp.any(rs > thresh)

    def body(s):
        i, x, r, p, rs, done = s
        ap = matvec(p)
        pap = rowdot(p, ap)
        active = rs > thresh
        alpha = jnp.where(active, rs / jnp.where(pap > 0, pap, 1), 0)
        x = rnd(x + alpha * p)
        r = r - alpha * ap
        rs_new = rowdot(r, r)
        beta = jnp.where(active, rs_new / jnp.where(rs != 0, rs, 1), 0)
        done = jnp.where(active & (rs_new <= thresh), i + 1, done)
        return i + 1, x, r, rnd(r + beta * p), rs_new, done

    i, x, _, _, _, done = jax.lax.while_loop(
        cond, body, (jnp.int32(0), x0, r, rnd(r), rs, done0))
    return x.T, i, done


def normal_equations(idx, vals, factors: Sequence, mode: int,
                     dtype=jnp.float32):
    """``(b, G, T)`` of mode ``mode``'s row systems, from the other modes'
    ``factors``."""
    fs = [None if e == mode else jnp.asarray(f, dtype)
          for e, f in enumerate(factors)]
    r = factors[mode].shape[1]
    block = max(1024, BLOCK_BYTES // (r * r * 4))
    return _normal_equations(idx, vals, fs, mode, factors[mode].shape[0],
                             block, jnp.dtype(dtype))


def solve_mode(idx, vals, factors: Sequence, mode: int, lam: float,
               cg_tol: float, cg_iters: int, dtype=jnp.float32):
    """One mode's ALS update from ``factors`` (the mode's own factor is the
    CG start). Returns ``(new factor, CG steps taken)``."""
    b, g, _ = normal_equations(idx, vals, factors, mode, dtype)
    x, steps, _ = _batched_cg(g, b, jnp.asarray(factors[mode], dtype), lam,
                              cg_tol, cg_iters, jnp.dtype(dtype))
    return x, int(steps)


def sweep(idx, vals, factors: Sequence, lam: float, cg_tol: float,
          cg_iters: int, dtype=jnp.float32) -> List[jax.Array]:
    """One ALS sweep, modes in order, each from the others' newest value."""
    fs = [jnp.asarray(f, dtype) for f in factors]
    for d in range(len(fs)):
        fs[d], _ = solve_mode(idx, vals, fs, d, lam, cg_tol, cg_iters,
                              dtype)
    return fs


def check_sweep(idx, vals, f_in: Sequence, f_out: Sequence, lam: float,
                cg_tol: float, cg_iters: int, margin: int) -> dict:
    """Check a sweep that took ``f_in`` to ``f_out`` against the reference,
    mode by mode from the same inputs: mode ``d``'s update saw ``f_out``
    for the modes before ``d`` and ``f_in`` for those after.

    The number compared is ``row_residual``, the worst relative residual
    ``|(G_i + lam I) x_i - b_i| / |b_i|`` of the sweep's answer ``x_i``,
    with ``G_i`` and ``b_i`` the reference's, over the rows that the
    reference's CG, started from the same point, brings to ``cg_tol`` at
    least ``margin`` steps before its bound. On those rows the
    configuration's guarantee holds with room to spare. On the rest, CG
    stops at its bound, and its answer there is set by rounding: two
    float32 runs of the same steps land far apart at nearly equal loss.
    A non-finite value anywhere in the answer reads ``inf``.

    Also returned, for the record: ``rows`` checked and ``rows_all`` with
    data, over all modes; the reference's CG steps per mode; and, for
    margins of 0 to 10 steps, the worst residual and the rows it covers in
    each mode."""
    margins = sorted({margin, *MARGINS})
    worst = dict.fromkeys(margins, -np.inf)
    rows = {m: [] for m in margins}
    rows_all, steps = 0, []
    for d in range(len(f_in)):
        inputs = [f_out[e] if e < d else f_in[e] for e in range(len(f_in))]
        b, g, t2 = normal_equations(idx, vals, inputs, d)
        x0 = jnp.asarray(f_in[d], jnp.float32)
        _, n, done = _batched_cg(g, b, x0, lam, cg_tol, cg_iters,
                                 jnp.dtype(jnp.float32))
        x = jnp.asarray(f_out[d], jnp.float32)
        rel, finite = (np.asarray(a) for a in _residual(g, b, lam, x))
        done, has_data = np.asarray(done), np.asarray(t2) > 0
        for m in margins:
            sel = has_data & (done >= 0) & (done <= cg_iters - m)
            w = float(np.max(rel[sel], initial=-np.inf))
            # a non-finite answer in any row, compared or not, is as far
            # off as can be
            worst[m] = max(worst[m], w if finite and w < np.inf else np.inf)
            rows[m].append(int(np.sum(sel)))
        rows_all += int(np.sum(has_data))
        steps.append(int(n))
    return {"row_residual": worst[margin], "rows": sum(rows[margin]),
            "rows_all": rows_all, "cg_steps": steps,
            "by_margin": {m: worst[m] for m in MARGINS},
            "rows_by_margin": {m: rows[m] for m in MARGINS}}


@jax.jit
def _residual(g, b, lam, x):
    """Each row's relative residual, and whether all of ``x`` is finite."""
    res = _matvec(g, lam, x.T) - b
    rel = jnp.sqrt(jnp.sum(res * res, axis=0) /
                   jnp.maximum(jnp.sum(b * b, axis=0), 1e-30))
    return jnp.where(jnp.isnan(rel), jnp.inf, rel), jnp.all(jnp.isfinite(x))
