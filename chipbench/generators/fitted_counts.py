"""A ratings tensor whose rows hold as many ratings as the published
statistics of the real data say, made on the device from a seed.

The configuration's ``counts`` gives, per mode, the median, least and
largest number of ratings a row of that mode holds in the whole data set
(``null``: rows drawn uniformly). Over all rows the counts average
``nnz_total / extent``. Each such mode gets a count profile: row ``k`` of
``n``, in order of decreasing count, holds
``exp(log(median) + sigma * Phi^-1(1 - (k + 1/2) / n))`` ratings, rounded
and clipped to ``[min, max]``, with ``sigma`` chosen so that the counts sum
to ``nnz_total``: a log-normal fitted to the median and the mean, cut at
the published extremes. The seed deals the counts to row ids in random
order.

One chip's share is a uniform sample of the whole data set's ratings: each
rating's row in such a mode is drawn with probability ``count / nnz_total``,
independently per mode, and the first ``nnz`` distinct (user, movie) pairs
in draw order are kept, since the real data holds one rating per pair. The
dedup is one device sort. Days are drawn uniformly. Ratings are
``clip(round(3.5 + sum(u * v * (1 + w)) + noise), 1, 5)`` with a rank-4
bias structure, as in the program's ``netflix_like``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from scipy.special import ndtri


def count_profile(n: int, median: float, lo: float, hi: float,
                  total: float) -> np.ndarray:
    """The ``n`` rows' counts in decreasing order (float64, whole numbers),
    log-normal about ``median``, clipped to ``[lo, hi]``, summing to about
    ``total``."""
    z = ndtri(1.0 - (np.arange(n) + 0.5) / n)
    prof = lambda s: np.clip(np.round(median * np.exp(s * z)), lo, hi)
    a, b = 0.0, 8.0
    if not prof(a).sum() <= total <= prof(b).sum():
        raise ValueError(f"no log-normal about median {median} in "
                         f"[{lo}, {hi}] sums to {total} over {n} rows")
    for _ in range(60):
        s = 0.5 * (a + b)
        a, b = (s, b) if prof(s).sum() < total else (a, s)
    return prof(b)


def _rows(key, counts, size):
    """``size`` row ids drawn with probability ``counts / sum(counts)``,
    the counts dealt to row ids in random order."""
    k_perm, k_draw = jax.random.split(key)
    weights = jax.random.permutation(k_perm, counts)
    cum = jnp.cumsum(weights)
    u = jax.random.randint(k_draw, (size,), 0, cum[-1], jnp.int32)
    return jnp.searchsorted(cum, u, side="right").astype(jnp.int32)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _generate(key, counts, shape, nnz):
    draw = max(2 * nnz, 1024)
    ks = jax.random.split(key, 8)
    cols = [_rows(jax.random.fold_in(ks[0], d), c, draw) if c is not None
            else jax.random.randint(jax.random.fold_in(ks[0], d), (draw,), 0,
                                    s, jnp.int32)
            for d, (c, s) in enumerate(zip(counts, shape))]
    ii, jj, kk = cols
    pos = jnp.arange(draw, dtype=jnp.int32)
    si, sj, sp = jax.lax.sort((ii, jj, pos), num_keys=3)
    repeat = jnp.concatenate([
        jnp.zeros((1,), bool), (si[1:] == si[:-1]) & (sj[1:] == sj[:-1])])
    n_unique = draw - jnp.sum(repeat)
    # draw positions of first occurrences, in draw order; repeats sort last
    first = jnp.sort(jnp.where(repeat, draw, sp))[:nnz]
    ii, jj, kk = ii[first], jj[first], kk[first]
    i_dim, j_dim, k_dim = shape
    r = 4
    bu = 0.5 * jax.random.normal(ks[3], (i_dim, r))
    bv = 0.5 * jax.random.normal(ks[4], (j_dim, r))
    bw = 0.2 * jax.random.normal(ks[5], (k_dim, r))
    base = 3.5 + jnp.sum(bu[ii] * bv[jj] * (1.0 + bw[kk]), axis=1)
    noise = 0.4 * jax.random.normal(ks[6], (nnz,))
    vals = jnp.clip(jnp.round(base + noise), 1.0, 5.0)
    return jnp.stack([ii, jj, kk], 1), vals, n_unique


def generate(key, cfg: dict, nnz: int):
    """``(indices (nnz, 3) int32, values (nnz,) float32)`` on the default
    device: exactly ``nnz`` distinct (user, movie) pairs."""
    shape = tuple(cfg["shape"])
    counts = [None if c is None else jnp.asarray(count_profile(
        n, c["median"], c["min"], c["max"], cfg["nnz_total"]), jnp.int32)
        for c, n in zip(cfg["counts"], shape)]
    idx, vals, n_unique = _generate(key, counts, shape, nnz)
    if int(n_unique) < nnz:
        raise RuntimeError(f"fitted_counts: {int(n_unique)} distinct pairs "
                           f"in {2 * nnz} draws, need {nnz}")
    return idx, vals
