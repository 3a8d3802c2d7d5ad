"""Pallas TPU kernel for bucketed all-at-once MTTKRP (tiled tier).

The scatter-add of MTTKRP is the part with no TPU-native analogue (the paper
uses CPU dense-buffer row accumulation). Our adaptation (DESIGN.md §3, §13):
the ingest-time CCSR bucketing (``repro.sparse.ccsr.bucketize``) groups
sorted nonzeros into fixed-capacity buckets spanning ``block_rows``
consecutive output rows, and the in-bucket scatter runs as either the
one-hot ``(block_rows × C) @ (C × block_r)`` MXU matmul or the segmented
cumsum reduction — chosen per :class:`~repro.kernels.tile.KernelTile`
(``schedule='auto'`` resolves by the break-even point).

Grid: (num_buckets / buckets_per_step, R blocks). Each step processes
``buckets_per_step`` buckets; within each bucket a ``fori_loop`` walks the
capacity in ``block_m`` tiles, so VMEM transients are Θ(block_m·block_r)
regardless of bucket capacity:

  1. gather factor rows for the tile's nonzeros (VPU),
  2. Hadamard-product with values in the input dtype (bf16 stays bf16),
  3. scatter into a (block_rows, block_r) accumulator in ``accum_dtype``
     (fp32 MXU accumulation for bf16 inputs).

Padding slots (``valid == False``) carry ``local_row == 0`` at the bucket
tail, which would break both schedules' key assumptions — the kernel scatter
key is ``where(valid, local_row, block_rows)``: monotone for the segmented
prefix trick, and matching no output row in the one-hot comparison.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.utils import pad_axis, round_up
from repro.kernels.tile import KernelTile, scatter_rows
from repro.sparse.ccsr import RowBlockBuckets


def _mttkrp_kernel(other_slots, block_rows, block_m, num_tiles, g, schedule,
                   acc_dtype, vals_ref, idx_ref, key_ref, *refs):
    factor_refs, out_ref = refs[:-1], refs[-1]
    block_r = out_ref.shape[-1]
    for gi in range(g):                      # static unroll over buckets

        def tile_body(t, acc, gi=gi):
            sl = pl.dslice(t * block_m, block_m)
            vals = vals_ref[gi, sl]          # (block_m,)
            idx = idx_ref[gi, sl, :]         # (block_m, nd)
            key = key_ref[gi, sl]            # (block_m,)
            prod = None
            for slot, f_ref in zip(other_slots, factor_refs):
                rows = jnp.take(f_ref[...], idx[:, slot], axis=0)
                prod = rows if prod is None else prod * rows
            prod = prod * vals[:, None]      # (block_m, block_r), input dtype
            return acc + scatter_rows(prod, key, block_rows, schedule,
                                      acc_dtype)

        acc = jax.lax.fori_loop(
            0, num_tiles, tile_body,
            jnp.zeros((block_rows, block_r), acc_dtype))
        out_ref[gi * block_rows:(gi + 1) * block_rows, :] = acc


def _pad_buckets(values, indices, key, block_m, g, fill_key):
    """Pad the capacity axis to a block_m multiple and the bucket axis to a
    buckets_per_step multiple; padding slots get value 0 / index 0 / key
    ``fill_key`` (past the valid local-row range)."""
    nb, c = values.shape
    cp, nbp = round_up(c, block_m), round_up(nb, g)
    if cp != c:
        values = pad_axis(values, cp, axis=1)
        indices = pad_axis(indices, cp, axis=1)
        key = pad_axis(key, cp, axis=1, value=fill_key)
    if nbp != nb:
        values = pad_axis(values, nbp, axis=0)
        indices = pad_axis(indices, nbp, axis=0)
        key = pad_axis(key, nbp, axis=0, value=fill_key)
    return values, indices, key, nbp, cp


def mttkrp_pallas(buckets: RowBlockBuckets,
                  factors: Sequence[Optional[jax.Array]],
                  block_r: Optional[int] = None,
                  tile: Optional[KernelTile] = None,
                  *, interpret: bool) -> jax.Array:
    """Bucketed MTTKRP. Returns (padded rows, R) in ``tile.accum_dtype``;
    callers slice to ``shape[mode]`` rows and cast. R must be a multiple of
    the resolved ``block_r`` (ops.py pads); capacity and bucket-count
    padding happen here."""
    tile = tile if tile is not None else KernelTile()
    nd = buckets.indices.shape[-1]
    mode = buckets.mode
    block_rows = buckets.block_rows
    other = tuple(d for d in range(nd) if d != mode and factors[d] is not None)
    fs = [factors[d] for d in other]
    r = fs[0].shape[1]
    block_r = min(block_r if block_r is not None else tile.block_r, r)
    if r % block_r:
        raise ValueError(f"R={r} % block_r={block_r} nonzero; pad first")
    c = buckets.values.shape[1]
    block_m = min(tile.block_m, round_up(c, 8))
    g = tile.buckets_per_step
    schedule = tile.resolved_schedule(block_rows, block_m)
    key = jnp.where(buckets.valid, buckets.local_row,
                    jnp.int32(block_rows)).astype(jnp.int32)
    values, indices, key, nbp, cp = _pad_buckets(
        buckets.values, buckets.indices, key, block_m, g, block_rows)
    grid = (nbp // g, r // block_r)
    in_specs = [
        pl.BlockSpec((g, cp), lambda b, j: (b, 0)),
        pl.BlockSpec((g, cp, nd), lambda b, j: (b, 0, 0)),
        pl.BlockSpec((g, cp), lambda b, j: (b, 0)),
    ] + [
        pl.BlockSpec((f.shape[0], block_r), lambda b, j: (0, j)) for f in fs
    ]
    kernel = functools.partial(_mttkrp_kernel, other, block_rows, block_m,
                               cp // block_m, g, schedule, tile.acc)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((g * block_rows, block_r),
                               lambda b, j: (b, j)),
        out_shape=jax.ShapeDtypeStruct((nbp * block_rows, r), tile.acc),
        interpret=interpret,
    )(values, indices, key, *fs)
    return out
