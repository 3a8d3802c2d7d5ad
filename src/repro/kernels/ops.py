"""jit'd wrappers dispatching between the Pallas kernels and the pure-jnp
reference paths, with shape padding to block multiples.

Dispatch policy, in one place (:func:`route`): each kernel family goes
either to its Pallas kernel or to the XLA (pure jnp) path.

* On a TPU a family goes to Pallas only if its kernel compiles for the
  chip. ``TPU_REFUSED`` names the families the TPU compiler refuses and
  why; ``tests/test_tpu_compile.py`` compiles each kernel for a described
  v5e, so a kernel that starts to compile fails its strict xfail there and
  its entry here has to go. Asking for a refused kernel (``use_pallas=True``
  or ``REPRO_USE_PALLAS=1``) raises with the compiler's reason: it never
  falls back quietly and never runs in interpret mode.
* Elsewhere (CPU) the XLA path is the default; ``use_pallas=True`` or
  ``REPRO_USE_PALLAS=1`` runs the kernels in interpret mode, which is how
  the kernels are tested here.

The platform is probed once, lazily (never at import): late device
initialization (``--force-host-devices``) must come first. The
environment variable is read per call, so tests can flip it.

All wrappers are shape-polymorphic over padding: inputs are padded to block
multiples and outputs sliced back. Block sizes come from a
:class:`~repro.kernels.tile.KernelTile` — explicit ``tile=`` wins, the
legacy ``block_m``/``block_r`` kwargs override individual fields, and with
neither the per-family process-wide table (``tile.current_tile``, where the
planner's autotuner installs measured winners) supplies the default. The
Pallas kernels accumulate in ``tile.accum_dtype`` (fp32 for bf16 inputs)
and the wrappers cast back to the jnp reference path's result dtype, so
both routes return identical dtypes.
"""
from __future__ import annotations

import dataclasses
import functools
import os
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.sparse_tensor import SparseTensor
from repro.core.utils import pad_axis, round_up
from repro.kernels import ref as kref
from repro.kernels import tile as ktile
from repro.kernels.cg_matvec import cg_matvec_pallas
from repro.kernels.mttkrp import mttkrp_pallas
from repro.kernels.tttp import tttp_pallas


# Mosaic lowers only a same-shape take_along_axis gather; every kernel here
# gathers block_m factor rows from an (I_d, block_r) ref with jnp.take.
_MOSAIC_ROW_GATHER = (
    "the TPU compiler (Mosaic) refuses the in-kernel row gather - jnp.take "
    "of block_m rows from an (I_d, block_r) factor ref fails with 'Shape "
    "mismatch in input, indices and output' at every geometry")

_BUCKET_BLOCK = (
    "; with buckets_per_step < 8 the (buckets_per_step, capacity) block is "
    "refused first, as not (8, 128)-tiled")

TPU_REFUSED = {
    "tttp": _MOSAIC_ROW_GATHER,
    "mttkrp": _MOSAIC_ROW_GATHER + _BUCKET_BLOCK,
    "cg_matvec": _MOSAIC_ROW_GATHER + _BUCKET_BLOCK,
}


@functools.cache
def _platform() -> str:
    return jax.devices()[0].platform


def route(family: str, use_pallas: Optional[bool] = None) -> str:
    """``"pallas"`` or ``"xla"`` for one kernel family (module docstring).
    ``use_pallas=None`` defers to ``REPRO_USE_PALLAS`` (unset: the platform
    default)."""
    if use_pallas is None and "REPRO_USE_PALLAS" in os.environ:
        use_pallas = os.environ["REPRO_USE_PALLAS"] == "1"
    if _platform() != "tpu":
        return "pallas" if use_pallas else "xla"
    refused = TPU_REFUSED.get(family)
    if refused is None:
        return "xla" if use_pallas is False else "pallas"
    if use_pallas:
        raise RuntimeError(f"the Pallas {family} kernel cannot run on this "
                           f"TPU: {refused}; unset REPRO_USE_PALLAS to take "
                           f"the XLA path")
    return "xla"


def _use_pallas(family: str, use_pallas: Optional[bool]) -> bool:
    return route(family, use_pallas) == "pallas"


def _interpret() -> bool:
    return _platform() != "tpu"


def _resolve_tile(family: str, tile: Optional[ktile.KernelTile],
                  block_m: Optional[int] = None,
                  block_r: Optional[int] = None) -> ktile.KernelTile:
    tile = tile if tile is not None else ktile.current_tile(family)
    overrides = {}
    if block_m is not None:
        overrides["block_m"] = block_m
    if block_r is not None:
        overrides["block_r"] = block_r
    return dataclasses.replace(tile, **overrides) if overrides else tile


def _pad_factors(factors, block_r):
    r = next(f.shape[1] for f in factors if f is not None)
    rp = round_up(r, block_r)
    if rp == r:
        return factors, r
    return [None if f is None else pad_axis(f, rp, axis=1) for f in factors], r


def _out_dtype(values_dtype, factors) -> jnp.dtype:
    """The jnp reference path's result dtype (promotion over the Hadamard
    chain) — the Pallas accumulator casts back to it."""
    return jnp.result_type(values_dtype,
                           *[f.dtype for f in factors if f is not None])


def tttp_values(st: SparseTensor, factors: Sequence[Optional[jax.Array]],
                use_pallas: Optional[bool] = None,
                block_m: Optional[int] = None,
                block_r: Optional[int] = None,
                tile: Optional[ktile.KernelTile] = None) -> jax.Array:
    """TTTP output values for a padded-COO SparseTensor. Vector factors are
    promoted to single-column matrices (paper's vector-list form)."""
    use_pallas = _use_pallas("tttp", use_pallas)
    factors = [None if f is None else (f[:, None] if f.ndim == 1 else f)
               for f in factors]
    t = _resolve_tile("tttp", tile, block_m=block_m, block_r=block_r)
    with obs.span("kernel/tttp", cap=st.cap, nnz=st.nnz,
                  pallas=use_pallas, tile=t.short()) as sp:
        vals = st.values * st.mask
        if not use_pallas:
            return sp.fence(kref.tttp_ref(vals, st.indices, factors))
        bm = min(t.block_m, round_up(st.cap, 8))
        mp = round_up(st.cap, bm * t.buckets_per_step)
        fs, r = _pad_factors(factors, t.block_r)
        out = tttp_pallas(pad_axis(vals, mp), pad_axis(st.indices, mp), fs,
                          block_m=bm,
                          block_r=min(t.block_r, round_up(r, 128)),
                          tile=t, interpret=_interpret())
        return sp.fence(out[:st.cap].astype(_out_dtype(vals.dtype, factors)))


def tttp(st: SparseTensor, factors, **kw) -> SparseTensor:
    return st.with_values(tttp_values(st, factors, **kw))


def mttkrp_bucketed(buckets, factors: Sequence[Optional[jax.Array]],
                    num_rows: Optional[int] = None,
                    use_pallas: Optional[bool] = None,
                    block_r: Optional[int] = None,
                    tile: Optional[ktile.KernelTile] = None) -> jax.Array:
    """All-at-once MTTKRP over ingest-time buckets; returns (num_rows, R)."""
    use_pallas = _use_pallas("mttkrp", use_pallas)
    num_rows = num_rows or buckets.shape[buckets.mode]
    t = _resolve_tile("mttkrp", tile, block_r=block_r)
    with obs.span("kernel/mttkrp_bucketed", mode=buckets.mode,
                  rows=num_rows, pallas=use_pallas, tile=t.short()) as sp:
        if use_pallas:
            fs, r = _pad_factors(factors, t.block_r)
            out = mttkrp_pallas(buckets, fs, tile=t, interpret=_interpret())
            dt = _out_dtype(buckets.values.dtype, factors)
            return sp.fence(out[:num_rows, :r].astype(dt))
        out = kref.mttkrp_bucketed_ref(buckets.values, buckets.indices,
                                       buckets.local_row, factors,
                                       buckets.mode, buckets.block_rows)
        return sp.fence(out[:num_rows])


def cg_matvec_bucketed(buckets, factors: Sequence[Optional[jax.Array]],
                       x: jax.Array, num_rows: Optional[int] = None,
                       use_pallas: Optional[bool] = None,
                       tile: Optional[ktile.KernelTile] = None) -> jax.Array:
    """Fused implicit-CG Gram matvec; buckets hold the Ω indicator values."""
    use_pallas = _use_pallas("cg_matvec", use_pallas)
    num_rows = num_rows or buckets.shape[buckets.mode]
    t = _resolve_tile("cg_matvec", tile)
    with obs.span("kernel/cg_matvec_bucketed", mode=buckets.mode,
                  rows=num_rows, pallas=use_pallas, tile=t.short()) as sp:
        if use_pallas:
            out = cg_matvec_pallas(buckets, factors, x, tile=t,
                                   interpret=_interpret())
            dt = _out_dtype(x.dtype, factors)
            return sp.fence(out[:num_rows].astype(dt))
        out = kref.cg_matvec_bucketed_ref(buckets.values, buckets.indices,
                                          buckets.local_row, factors, x,
                                          buckets.mode, buckets.block_rows)
        return sp.fence(out[:num_rows])
