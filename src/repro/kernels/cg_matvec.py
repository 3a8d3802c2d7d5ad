"""Pallas TPU kernel: fused implicit-CG Gram matvec (paper §2.2 + eq. 3).

Computes, in ONE pass over the nonzeros (per bucket),

    z_n    = ω_n Σ_s (Π_{d≠mode} A_d[i_d(n), s]) · x[i_mode(n), s]   (TTTP)
    y[i,r] = Σ_{n: i_mode(n)=i} z_n · Π_{d≠mode} A_d[i_d(n), r]      (MTTKRP)

This is the paper's key insight made kernel-level: the Khatri-Rao gather
(Π A_d rows) is computed once and reused for both the TTTP and MTTKRP halves,
and the (m, R) intermediate that pairwise contraction would materialize never
exists. The scatter half uses the tile's schedule — one-hot MXU matmul or
segmented cumsum reduction — exactly as in ``mttkrp.py``.

Grid: (num_buckets / buckets_per_step,). Full-R factor/x tiles are held in
VMEM — implicit-CG ranks (R ≤ ~512) fit comfortably (the TTTP half reduces
over all of R, so R-slicing would need two passes; ``tile.block_r`` is
ignored here). The capacity axis is walked in ``block_m`` tiles by a
``fori_loop`` with a (block_rows, R) accumulator in ``accum_dtype``, so
VMEM transients stay Θ(block_m·R) regardless of bucket capacity.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.utils import round_up
from repro.kernels.mttkrp import _pad_buckets
from repro.kernels.tile import KernelTile, scatter_rows
from repro.sparse.ccsr import RowBlockBuckets


def _cg_matvec_kernel(other_slots, mode, block_rows, block_m, num_tiles, g,
                      schedule, acc_dtype,
                      omega_ref, idx_ref, key_ref, *refs):
    x_ref = refs[-2]
    out_ref = refs[-1]
    factor_refs = refs[:-2]
    r = out_ref.shape[-1]
    for gi in range(g):                      # static unroll over buckets

        def tile_body(t, acc, gi=gi):
            sl = pl.dslice(t * block_m, block_m)
            omega = omega_ref[gi, sl]        # (block_m,)
            idx = idx_ref[gi, sl, :]         # (block_m, nd)
            key = key_ref[gi, sl]            # (block_m,)
            kr = None
            for slot, f_ref in zip(other_slots, factor_refs):
                rows = jnp.take(f_ref[...], idx[:, slot], axis=0)
                kr = rows if kr is None else kr * rows     # input dtype
            xrows = jnp.take(x_ref[...], idx[:, mode], axis=0)
            z = (omega.astype(acc_dtype)
                 * jnp.sum((kr * xrows).astype(acc_dtype), axis=1))
            contrib = z[:, None] * kr.astype(acc_dtype)    # (block_m, R)
            return acc + scatter_rows(contrib, key, block_rows, schedule,
                                      acc_dtype)

        acc = jax.lax.fori_loop(
            0, num_tiles, tile_body, jnp.zeros((block_rows, r), acc_dtype))
        out_ref[gi * block_rows:(gi + 1) * block_rows, :] = acc


def cg_matvec_pallas(buckets: RowBlockBuckets,
                     factors: Sequence[Optional[jax.Array]],
                     x: jax.Array, tile: Optional[KernelTile] = None,
                     *, interpret: bool) -> jax.Array:
    """Fused Gram matvec over Ω-pattern buckets (bucketed over ``mode``).

    ``buckets.values`` must hold the Ω indicator (1.0 at observed entries,
    0 padding). Returns (padded rows, R) in ``tile.accum_dtype``; callers
    slice to the true row count and cast."""
    tile = tile if tile is not None else KernelTile()
    nd = buckets.indices.shape[-1]
    mode = buckets.mode
    block_rows = buckets.block_rows
    other = tuple(d for d in range(nd) if d != mode and factors[d] is not None)
    fs = [factors[d] for d in other]
    r = x.shape[1]
    c = buckets.values.shape[1]
    block_m = min(tile.block_m, round_up(c, 8))
    g = tile.buckets_per_step
    schedule = tile.resolved_schedule(block_rows, block_m)
    key = jnp.where(buckets.valid, buckets.local_row,
                    jnp.int32(block_rows)).astype(jnp.int32)
    values, indices, key, nbp, cp = _pad_buckets(
        buckets.values, buckets.indices, key, block_m, g, block_rows)
    grid = (nbp // g,)
    in_specs = [
        pl.BlockSpec((g, cp), lambda b: (b, 0)),
        pl.BlockSpec((g, cp, nd), lambda b: (b, 0, 0)),
        pl.BlockSpec((g, cp), lambda b: (b, 0)),
    ] + [
        pl.BlockSpec((f.shape[0], r), lambda b: (0, 0)) for f in fs
    ] + [
        pl.BlockSpec((x.shape[0], r), lambda b: (0, 0)),
    ]
    kernel = functools.partial(_cg_matvec_kernel, other, mode, block_rows,
                               block_m, cp // block_m, g, schedule, tile.acc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((g * block_rows, r), lambda b: (b, 0)),
        out_shape=jax.ShapeDtypeStruct((nbp * block_rows, r), tile.acc),
        interpret=interpret,
    )(values, indices, key, *fs, x)
