"""Pallas TPU kernel for TTTP (paper §3.2), tiled tier.

Grid: (value super-blocks, R blocks). Each grid step owns a super-block of
``block_m · buckets_per_step`` nonzeros and walks it in ``block_m`` tiles
with a ``fori_loop`` — VMEM transients are Θ(block_m · block_r) regardless
of the super-block size. Per tile the kernel gathers up to ``block_m``
factor rows per mode from VMEM-resident factor column-slices, forms the
Hadamard product on the VPU in the input dtype (bf16 stays bf16), reduces
the R tile in ``accum_dtype`` (fp32 for bf16 inputs), and accumulates into
the per-nonzero output slice. Accumulation over the R grid dimension
follows the standard revisiting-grid pattern (init at r==0); the output is
in ``accum_dtype`` — ops.py casts back.

Blocking / memory notes (TPU target, validated in interpret mode on CPU):
* value/index tiles are (block_m,) / (block_m, ndim) VMEM slices; block_m is
  a multiple of 8 (sublane) — default 1024;
* factor tiles are (I_d, block_r) column slices; block_r multiple of 128
  (lane) — the R grid axis is the paper's H-slicing realized as a grid
  dimension, bounding VMEM at Θ(Σ I_d · block_r);
* for factor matrices too large for VMEM the production path keeps factors in
  HBM (``memory_space=ANY``) and DMA-streams gathered rows; on this CPU
  container we validate the VMEM-resident variant only (DESIGN.md §3).
* the row gather uses ``jnp.take`` along axis 0, which the TPU compiler
  refuses (``kernels.ops.TPU_REFUSED``), so on a TPU this kernel does not
  run yet; padded entries carry value 0 and index 0, so they contribute 0.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.tile import KernelTile


def _tttp_kernel(nd_present, block_m, num_tiles, acc_dtype,
                 vals_ref, idx_ref, *refs):
    factor_refs, out_ref = refs[:-1], refs[-1]
    r_idx = pl.program_id(1)

    @pl.when(r_idx == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    def tile_body(t, carry):
        sl = pl.dslice(t * block_m, block_m)
        idx = idx_ref[sl, :]
        prod = None
        for slot, f_ref in enumerate(factor_refs):
            rows = jnp.take(f_ref[...], idx[:, nd_present[slot]], axis=0)
            prod = rows if prod is None else prod * rows
        partial = jnp.sum(prod.astype(acc_dtype), axis=1)   # (block_m,)
        out_ref[sl] += vals_ref[sl].astype(acc_dtype) * partial
        return carry

    jax.lax.fori_loop(0, num_tiles, tile_body, 0)


def tttp_pallas(values: jax.Array, indices: jax.Array,
                factors: Sequence[Optional[jax.Array]],
                block_m: Optional[int] = None,
                block_r: Optional[int] = None,
                tile: Optional[KernelTile] = None,
                *, interpret: bool) -> jax.Array:
    """TTTP on padded COO arrays. ``values (m,)``, ``indices (m, nd)``;
    ``factors[d]`` is ``(shape[d], R)`` or None. m must be a multiple of
    ``block_m · buckets_per_step`` and R of ``block_r`` (ops.py pads).
    Returns (m,) in ``tile.accum_dtype``."""
    tile = tile if tile is not None else KernelTile()
    m = values.shape[0]
    nd = indices.shape[1]
    present = tuple(d for d, f in enumerate(factors) if f is not None)
    fs = [factors[d] for d in present]
    r = fs[0].shape[1]
    block_m = min(block_m if block_m is not None else tile.block_m, m)
    block_r = min(block_r if block_r is not None else tile.block_r, r)
    step = block_m * tile.buckets_per_step
    if m % step or r % block_r:
        raise ValueError(f"m={m} % (block_m·g)={step} or R={r} % block_r="
                         f"{block_r} nonzero; pad first")
    grid = (m // step, r // block_r)
    in_specs = [
        pl.BlockSpec((step,), lambda i, j: (i,)),
        pl.BlockSpec((step, nd), lambda i, j: (i, 0)),
    ] + [
        pl.BlockSpec((f.shape[0], block_r), lambda i, j: (0, j)) for f in fs
    ]
    kernel = functools.partial(_tttp_kernel, present, block_m,
                               step // block_m, tile.acc)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((step,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((m,), tile.acc),
        interpret=interpret,
    )(values, indices, *fs)
