"""Distributed execution layer: the Cyclops role, played by shard_map.

The completion algorithms (``repro.core.completion``) are written against an
:class:`AxisCtx` that abstracts over local vs. distributed execution — user
algorithm code is *parallelism-oblivious*, the paper's central thesis. The
ctx primitives here (``tttp_ctx``/``mttkrp_ctx``/``reduce_mode_ctx``/
``mttkrp_rowsharded``) are shims over the planner executor
(``repro.planner``, DESIGN.md §9): the ctx rides into the plan's
distribution signature and dispatch applies the collectives. The mapping
(DESIGN.md §4):

* nonzeros sharded over the data axes (flattened ``("pod","data")`` on the
  multi-pod mesh) — the paper's distribution of observed entries;
* factor matrices **column-sharded over the model axis** — the paper's
  H-slicing of R realized as a mesh axis — and replicated over data axes;
* TTTP ⇒ local partial inner products + ``psum(model)``;
* MTTKRP ⇒ local segment-sum + ``psum(data)`` (column slices stay local);
* CG row-wise dots ⇒ ``psum(model)``.

Also provides the paper-faithful **butterfly sparse all-reduce** (Fig. 1):
recursive-halving reduce-scatter over linearized-coordinate ranges with local
hypersparse summation at each step, followed by an all-gather — used for
reducing sparse blocks with device-dependent patterns.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.sparse_tensor import SparseTensor
from repro.core.utils import axis_size
from repro.sparse import ops as sops


@dataclasses.dataclass(frozen=True)
class AxisCtx:
    """Names of mesh axes inside a shard_map region (None ⇒ local run)."""
    data: Optional[object] = None   # axis name or tuple of names
    model: Optional[str] = None

    def psum_data(self, x):
        if self.data is None:
            return x
        with obs.scope("psum"):
            return jax.lax.psum(x, self.data)

    def psum_model(self, x):
        if self.model is None:
            return x
        with obs.scope("psum"):
            return jax.lax.psum(x, self.model)

    def data_size(self) -> int:
        if self.data is None:
            return 1
        names = self.data if isinstance(self.data, tuple) else (self.data,)
        return int(np.prod([axis_size(n) for n in names]))

    def model_size(self) -> int:
        return axis_size(self.model) if self.model is not None else 1

    def model_index(self):
        return jax.lax.axis_index(self.model) if self.model is not None else 0


LOCAL = AxisCtx()


def make_mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    """``jax.make_mesh`` with ``Auto`` axes. The default ``Explicit`` axes
    make eager gathers on sharded arrays raise ``ShardingTypeError``; this
    code places its own collectives inside ``shard_map`` and leaves the rest
    to the partitioner."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


@dataclasses.dataclass
class DistLayout:
    """Mesh + specs for the completion workload."""
    mesh: Mesh
    data_axes: tuple            # e.g. ("data",) or ("pod", "data")
    model_axis: Optional[str]   # e.g. "model"; None = replicated factors

    @property
    def ctx(self) -> AxisCtx:
        data = self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        return AxisCtx(data=data, model=self.model_axis)

    def nnz_spec(self) -> P:
        return P(self.data_axes if len(self.data_axes) > 1 else self.data_axes[0])

    def sparse_specs(self, st: SparseTensor):
        """SparseTensor-shaped pytree of PartitionSpecs (nonzeros over the
        data axes; the valid mask shards with the values)."""
        d = self.data_axes if len(self.data_axes) > 1 else self.data_axes[0]
        idx_spec = P(d, None)
        val_spec = P(d) if st.values.ndim == 1 else P(d, None)
        return SparseTensor(idx_spec, val_spec, P(d), st.shape, st.nnz,
                            st.sorted_mode, st.nnz_rows)

    def factor_spec(self) -> P:
        return P(None, self.model_axis)  # rows replicated, columns H-sliced

    def shard(self, fn: Callable, in_specs, out_specs) -> Callable:
        return jax.shard_map(fn, mesh=self.mesh, in_specs=in_specs,
                             out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# ctx-parameterized primitives (used inside completion algorithms)
#
# These are thin shims over the planner executor (DESIGN.md §9): the
# contraction is classified, candidate paths ranked with the communication
# terms the ctx implies, and the winner dispatched with the ctx's psums
# applied inside dispatch — a single execution layer from IR to mesh.
# ---------------------------------------------------------------------------

def tttp_ctx(st: SparseTensor, factors, ctx: AxisCtx,
             kernel_fn=None, path: Optional[str] = None) -> SparseTensor:
    """TTTP under AxisCtx: factors column-sharded over the model axis ⇒
    local partial inner products + psum(model), via planner dispatch.
    ``path`` forces a planner candidate; ``kernel_fn`` bypasses the planner
    with a raw values-kernel (benchmark escape hatch)."""
    if kernel_fn is not None:
        partial = kernel_fn(st, factors)
        return st.with_values(st.values * ctx.psum_model(partial))
    from repro.planner import planned_tttp
    return planned_tttp(st, factors, path=path, ctx=ctx)


def mttkrp_ctx(st: SparseTensor, factors, mode: int, ctx: AxisCtx,
               path: Optional[str] = None) -> jax.Array:
    """MTTKRP under AxisCtx via planner dispatch: local contraction + psum
    over data axes (applied inside dispatch). Output is (rows, R_local):
    replicated over data, column-sharded over model."""
    from repro.planner import planned_mttkrp
    return planned_mttkrp(st, factors, mode, path=path, ctx=ctx)


def reduce_mode_ctx(st: SparseTensor, mode: int, ctx: AxisCtx) -> jax.Array:
    """``einsum('ijk->i')``-style sparse mode reduction under AxisCtx (local
    segment-sum + psum(data)), via planner dispatch."""
    from repro.planner import planned_reduce
    return planned_reduce(st, (mode,), ctx=ctx)


def rowdot_ctx(a: jax.Array, b: jax.Array, ctx: AxisCtx) -> jax.Array:
    """Row-wise inner products of column-sharded (rows, R_local) matrices."""
    return ctx.psum_model(jnp.sum(a * b, axis=-1))


def sqnorm_ctx(a: jax.Array, ctx: AxisCtx) -> jax.Array:
    return ctx.psum_model(jnp.sum(jnp.square(a)))


# ---------------------------------------------------------------------------
# butterfly sparse all-reduce (paper Fig. 1), k=2
# ---------------------------------------------------------------------------

def sparse_allreduce_butterfly(st: SparseTensor, axis_name: str) -> SparseTensor:
    """All-reduce sparse blocks with device-dependent patterns over a mesh
    axis: recursive halving on linearized-coordinate ranges (reduce-scatter)
    with hypersparse local summation per step, then recursive doubling
    (all-gather). Static capacities throughout; per-step message capacity is
    the full block capacity (mask-padded), so the win vs. dense all-reduce is
    the Θ(m) payload, as in the paper."""
    size = axis_size(axis_name)
    rank = jax.lax.axis_index(axis_name)
    steps = int(np.log2(size))
    assert 2 ** steps == size, "butterfly requires power-of-two axis"
    # Owned range is tracked via mode-0 coordinate intervals.
    lo, hi = jnp.int32(0), jnp.int32(st.shape[0])
    cur = st
    # reduce-scatter (recursive halving)
    for s in range(steps):
        bit = (rank >> s) & 1
        mid = lo + (hi - lo) // 2
        # partner differs in bit s
        perm = [(i, i ^ (1 << s)) for i in range(size)]
        keep_lo = jnp.where(bit == 0, lo, mid)
        keep_hi = jnp.where(bit == 0, mid, hi)
        rows = cur.indices[:, 0]
        mine = (rows >= keep_lo) & (rows < keep_hi) & cur.mask
        theirs = ~mine & cur.mask
        vals = cur.masked_values()
        recv_idx = jax.lax.ppermute(cur.indices, axis_name, perm)
        recv_vals = jax.lax.ppermute(jnp.where(theirs, vals, 0.0),
                                     axis_name, perm)
        recv_valid = jax.lax.ppermute(theirs, axis_name, perm)
        a = SparseTensor(cur.indices, jnp.where(mine, vals, 0.0), mine,
                         cur.shape)
        b = SparseTensor(recv_idx, recv_vals, recv_valid, cur.shape)
        cur = sops.sparse_add_union(a, b)
        # halve capacity: after the union-sort, valid owned entries are first
        cur = SparseTensor(cur.indices[:st.cap], cur.values[:st.cap],
                           cur.valid[:st.cap], cur.shape)
        lo, hi = keep_lo, keep_hi
    # all-gather (recursive doubling): owned ranges are disjoint, so the
    # union-sum is exact; per-step capacity doubles back up to size*cap.
    out = cur
    for s in range(steps - 1, -1, -1):
        perm = [(i, i ^ (1 << s)) for i in range(size)]
        recv_idx = jax.lax.ppermute(out.indices, axis_name, perm)
        recv_vals = jax.lax.ppermute(out.masked_values(), axis_name, perm)
        recv_valid = jax.lax.ppermute(out.valid, axis_name, perm)
        out = sops.sparse_add_union(
            out, SparseTensor(recv_idx, recv_vals, recv_valid, out.shape))
    return out


# ---------------------------------------------------------------------------
# Row-sharded factors with H-sliced, overlap-friendly gathers (paper Fig. 2)
#
# ``multilinear_rowsharded`` / ``_mttkrp_rowsharded_impl`` are the raw
# collective kernels the planner's "rowsharded" path dispatches onto;
# ``mttkrp_rowsharded`` is the public planner shim.
# ---------------------------------------------------------------------------

def multilinear_rowsharded(st: SparseTensor, factors_local, ctx: AxisCtx,
                           h_slices: int = 1) -> jax.Array:
    """Σ_r Π_d A_d[i_d, r] with factor ROWS sharded over the data axes —
    the paper's memory-scalable distribution: each slice's columns are
    all-gathered (payload Θ(I·R/H)), used, and discarded; the gather for
    slice h+1 is issued before slice h's compute consumes its operand, so
    the latency-hiding scheduler overlaps communication with compute
    (paper Fig. 2's per-slice redistribution, plus overlap)."""
    r = next(f.shape[1] for f in factors_local if f is not None)
    rs = -(-r // max(h_slices, 1))
    axis = ctx.data

    def gather_slice(h):
        out = []
        for f in factors_local:
            if f is None:
                out.append(None)
                continue
            sl = f[:, h * rs:(h + 1) * rs]
            out.append(jax.lax.all_gather(sl, axis, axis=0, tiled=True))
        return out

    acc = jnp.zeros((st.cap,), st.values.dtype)
    nxt = gather_slice(0)
    for h in range(max(h_slices, 1)):
        cur = nxt
        if h + 1 < h_slices:
            nxt = gather_slice(h + 1)   # independent of cur's consumers
        prod = None
        for d, f in enumerate(cur):
            if f is None:
                continue
            rows = f[st.indices[:, d]]
            prod = rows if prod is None else prod * rows
        acc = acc + jnp.sum(prod, axis=1)
    return acc


def mttkrp_rowsharded(st: SparseTensor, factors_local, mode: int,
                      ctx: AxisCtx, h_slices: int = 1) -> jax.Array:
    """MTTKRP with factor ROWS sharded over the data axes, via the planner's
    ``rowsharded`` path: per slice, gather the non-target factors' columns,
    segment-sum locally, then reduce-scatter output rows to their owners
    (Θ(I·R/H) transients and payloads). Output is (rows_local, R)."""
    from repro.planner import planned_mttkrp
    return planned_mttkrp(st, factors_local, mode, ctx=ctx, rowsharded=True,
                          h_slices=h_slices)


def _mttkrp_rowsharded_impl(st: SparseTensor, factors_local, mode: int,
                            ctx: AxisCtx, h_slices: int = 1) -> jax.Array:
    """Raw gather/compute/reduce-scatter kernel behind
    :func:`mttkrp_rowsharded` (invoked by planner dispatch)."""
    r = next(f.shape[1] for f in factors_local if f is not None)
    rs = -(-r // max(h_slices, 1))
    axis = ctx.data
    n_rows = st.shape[mode]
    # the target mode's rows are sharded evenly over the data axes (the
    # target factor itself is not an operand of the contraction)
    p = ctx.data_size()
    if n_rows % p:
        raise ValueError(
            f"row-sharded MTTKRP needs mode {mode}'s extent ({n_rows}) "
            f"divisible by the data-axis size ({p}) — the reduce-scatter "
            f"returns equal row blocks to their owners")
    n_rows_local = n_rows // p
    rows = st.indices[:, mode]
    cols = []
    for h in range(max(h_slices, 1)):
        prod = (st.values * st.mask)[:, None]
        for d, f in enumerate(factors_local):
            if d == mode or f is None:
                continue
            sl = jax.lax.all_gather(f[:, h * rs:(h + 1) * rs], axis,
                                    axis=0, tiled=True)
            prod = prod * sl[st.indices[:, d]]
        part = jax.ops.segment_sum(prod, rows, num_segments=n_rows)
        part = part.reshape(-1, n_rows_local, part.shape[1])
        cols.append(jax.lax.psum_scatter(part, axis, scatter_dimension=0,
                                         tiled=False))
    return jnp.concatenate(cols, axis=-1)[:, :r] if len(cols) > 1 \
        else cols[0][:, :r]
