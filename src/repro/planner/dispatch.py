"""Plan dispatcher — lowers a chosen (IR, path) onto the kernel library,
applying the collectives the plan's AxisCtx implies (one execution layer
from IR to mesh, DESIGN.md §9).

Each contraction family maps onto ``repro.sparse.ops`` / ``repro.kernels``
(which internally select the Pallas kernels when their block-size
preconditions hold, jnp fallbacks otherwise):

* REDUCE  → linearized multi-mode segment-sum (arbitrary kept-mode subsets),
  psum(data) on the dense output;
* TTTP    → ``kernels.ops.tttp`` (Pallas/ref), pairwise or H-sliced variants;
  under a model axis (column-sliced R) the local partial values are
  psum(model)'d;
* TTM     → dense-output scatter-add or hypersparse compressed-key kernel,
  psum(data) on the dense output;
* MTTKRP  → all-at-once gather–product–segment-sum, CCSR-bucketed kernel,
  pairwise T-first / KR-first, or the generalized multi-output-mode form;
  psum(data) on the (rows, R_local) output;
* CG_MATVEC → the eq.-3 weighted Gram matvec: the TTTP half is psum(model)'d
  before the MTTKRP half, the output psum(data)'d;
* rowsharded → factor ROWS sharded over the data axes (paper Fig. 2):
  per-slice all-gather + local compute (+ reduce-scatter for MTTKRP),
  dispatched onto ``repro.core.distributed``'s collective kernels.

Every path of a given IR computes the same einsum, so forcing paths is a
numerical no-op (tested in ``tests/test_planner.py``). All jnp paths are
jit-safe; the ``bucketed``/``fused`` paths consume the ingest-time cached
``RowBlockBuckets`` view on the SparseTensor (``SparseTensor.row_buckets``)
— values are re-gathered through the cached pattern per call — and fall
back to ``all_at_once``/``tttp_mttkrp`` when no pattern is available under
tracing, bumping a ``dispatch/fallback/*`` obs counter at trace time.
"""
from __future__ import annotations

import math
import time
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.core import tttp as core_tttp
from repro.core.distributed import AxisCtx, LOCAL
from repro.core.sparse_tensor import SparseTensor
from repro.core.utils import linearize
from repro.kernels import ops as kops
from repro.planner import ir as pir
from repro.planner.config import PlannerConfig, default_config
from repro.planner.cost import _sliced_h
from repro.sparse import ops as sops


def _split_operands(ir: pir.ContractionIR, operands: Sequence):
    st = operands[ir.sparse_pos]
    dense_ops = [operands[i] for i in ir.dense_positions]
    return st, dense_ops


def _factors_by_mode(ir: pir.ContractionIR,
                     dense_ops: Sequence[jax.Array]) -> List[Optional[jax.Array]]:
    """Length-N factor list with None at uncovered modes."""
    factors: List[Optional[jax.Array]] = [None] * len(ir.sparse.shape)
    for mode, f in zip(ir.factor_modes, dense_ops):
        factors[mode] = f
    return factors


def _reorder(res: jax.Array, canon: str, out: str) -> jax.Array:
    """Transpose a result with axis order ``canon`` into axis order ``out``."""
    if canon == out:
        return res
    return jnp.transpose(res, tuple(canon.index(c) for c in out))


def _densified_einsum(ir: pir.ContractionIR, st: SparseTensor,
                      dense_ops: Sequence) -> jax.Array:
    """Dense fallback preserving the original operand order (the sparse
    operand need not be first). ``optimize="greedy"``: jnp.einsum's default
    exhaustive path search is exponential in operand count and hangs at
    trace time on order-5 CG matvecs (11 operands); greedy is near-optimal
    for these factor-matrix chains and linear-time."""
    args: List = [None] * len(ir.operands)
    args[ir.sparse_pos] = st.todense()
    for pos, op in zip(ir.dense_positions, dense_ops):
        args[pos] = op
    return jnp.einsum(ir.expr, *args, optimize="greedy")


# ---------------------------------------------------------------------------
# per-kind executors
# ---------------------------------------------------------------------------

def _exec_reduce(ir: pir.ContractionIR, st: SparseTensor, path: str,
                 ctx: AxisCtx):
    if path == "dense" and st.dense_dim is None:
        return ctx.psum_data(_densified_einsum(ir, st, ()))
    # trailing-dense values ride along unreduced (reduce_mode semantics);
    # the densify fallback cannot express them, so it also lands here
    if not ir.keep_modes:
        return ctx.psum_data(st.sum())
    kept_shape = tuple(st.shape[d] for d in ir.keep_modes)
    k = int(math.prod(kept_shape))
    lin = linearize(st.indices[:, list(ir.keep_modes)], kept_shape)
    out = jax.ops.segment_sum(st.masked_values(), lin, num_segments=k)
    return ctx.psum_data(out.reshape(kept_shape + out.shape[1:]))


def _exec_tttp(ir: pir.ContractionIR, st: SparseTensor, dense_ops, path: str,
               ctx: AxisCtx, config: PlannerConfig):
    factors = _factors_by_mode(ir, dense_ops)
    if path == "rowsharded":
        from repro.core.distributed import multilinear_rowsharded
        acc = multilinear_rowsharded(st, factors, ctx,
                                     h_slices=config.h_slices)
        return st.with_values(st.values * acc)
    if path == "all_at_once":
        res = kops.tttp(st, factors)
    elif path == "sliced":
        res = core_tttp.tttp_sliced(st, factors, _sliced_h(ir.rank_size))
    elif path == "pairwise":
        res = core_tttp.tttp_pairwise(st, factors)
    elif path == "dense":
        # Form the dense multilinear model over the covered modes only and
        # sample it per entry. (Gathering from a densified *result* would
        # double-count duplicate COO coordinates.)
        s_term = ir.sparse_term
        covered = sorted(ir.factor_modes)
        model_out = "".join(s_term[d] for d in covered)
        terms = [ir.operands[i].term for i in ir.dense_positions]
        model = jnp.einsum(",".join(terms) + "->" + model_out, *dense_ops)
        vals = st.values * model[tuple(st.indices[:, d] for d in covered)]
        res = st.with_values(vals)
    else:
        raise ValueError(f"unknown TTTP path {path!r}")
    if ctx.model is not None:
        # values are linear in the per-column partial inner products, so
        # the psum over column slices applies directly to them
        res = res.with_values(ctx.psum_model(res.values))
    return res


def _exec_ttm(ir: pir.ContractionIR, st: SparseTensor, dense_ops, path: str,
              ctx: AxisCtx):
    (w,) = dense_ops
    mode = ir.contract_mode
    s_term = ir.sparse_term
    canon = "".join(c for c in s_term if s_term.index(c) != mode) + ir.rank_index
    if path == "dense_output":
        res = sops.ttm_dense_output(st, w, mode)
    elif path == "hypersparse":
        res = sops.ttm_hypersparse(st, w, mode).todense()
    elif path == "dense":
        return ctx.psum_data(_densified_einsum(ir, st, dense_ops))
    else:
        raise ValueError(f"unknown TTM path {path!r}")
    return ctx.psum_data(_reorder(res, canon, ir.out))


def _mttkrp_general(ir: pir.ContractionIR, st: SparseTensor,
                    factors: Sequence[Optional[jax.Array]]) -> jax.Array:
    """All-at-once partial MTTKRP with any kept-mode subset: gather factor
    rows, multiply, segment-sum over the linearized kept key."""
    prod = st.masked_values()[:, None]
    for d, f in enumerate(factors):
        if f is not None:
            prod = prod * f[st.indices[:, d]]
    kept_shape = tuple(st.shape[d] for d in ir.keep_modes)
    k = int(math.prod(kept_shape)) if kept_shape else 1
    lin = linearize(st.indices[:, list(ir.keep_modes)], kept_shape)
    res = jax.ops.segment_sum(prod, lin, num_segments=k)
    return res.reshape(kept_shape + (res.shape[-1],))


def _exec_mttkrp(ir: pir.ContractionIR, st: SparseTensor, dense_ops,
                 path: str, ctx: AxisCtx, config: PlannerConfig):
    if path == "dense":
        return ctx.psum_data(_densified_einsum(ir, st, dense_ops))
    factors = _factors_by_mode(ir, dense_ops)
    out_sparse = ir.out.replace(ir.rank_index, "")
    canon = out_sparse + ir.rank_index           # kept modes in out order, r last
    if not pir.is_classic_mttkrp(ir):
        if path != "all_at_once":
            raise ValueError(f"path {path!r} requires the classic MTTKRP "
                             f"shape (one kept mode, all others contracted)")
        return ctx.psum_data(
            _reorder(_mttkrp_general(ir, st, factors), canon, ir.out))
    mode = ir.keep_modes[0]
    if path == "rowsharded":
        from repro.core.distributed import _mttkrp_rowsharded_impl
        # the reduce-scatter inside already sums over the data axes
        res = _mttkrp_rowsharded_impl(st, factors, mode, ctx,
                                      h_slices=config.h_slices)
        return _reorder(res, canon, ir.out)
    if path == "bucketed":
        buckets = st.row_buckets(mode, config.block_rows)
        if buckets is not None:
            res = kops.mttkrp_bucketed(buckets, factors,
                                       num_rows=st.shape[mode])
        else:                                    # tracing, no cached pattern
            obs.counter_add("dispatch/fallback/mttkrp_bucketed")
            res = sops.mttkrp(st, factors, mode)
    elif path == "all_at_once":
        res = sops.mttkrp(st, factors, mode)
    elif path == "t_first":
        res = sops.mttkrp_pairwise_t_first(st, factors, mode)
    elif path == "kr_first":
        res = sops.mttkrp_pairwise_kr_first(st, factors, mode)
    else:
        raise ValueError(f"unknown MTTKRP path {path!r}")
    return ctx.psum_data(_reorder(res, canon, ir.out))


def _cg_factor_groups(ir: pir.ContractionIR, dense_ops: Sequence):
    """Split the CG_MATVEC dense operands into the kept-rank (MTTKRP half)
    and contracted-rank (TTTP half) factor lists, indexed by sparse mode."""
    nd = len(ir.sparse.shape)
    s_term = ir.sparse_term
    r_fac: List[Optional[jax.Array]] = [None] * nd
    s_fac: List[Optional[jax.Array]] = [None] * nd
    for pos, op in zip(ir.dense_positions, dense_ops):
        t = ir.operands[pos].term
        d = s_term.index(t[0])
        if t[1] == ir.rank_index:
            r_fac[d] = op
        else:
            s_fac[d] = op
    return r_fac, s_fac


def _exec_cg_matvec(ir: pir.ContractionIR, st: SparseTensor, dense_ops,
                    path: str, ctx: AxisCtx, config: PlannerConfig):
    """Weighted Gram matvec (paper eq. 3): values of ``st`` are the
    curvature weights ω_n; ``s_fac[mode]`` is the CG direction x. Under a
    model axis the TTTP half's partial is psum(model)'d before the MTTKRP
    half; the output is psum(data)'d."""
    if path == "dense":
        return ctx.psum_data(_densified_einsum(ir, st, dense_ops))
    mode = ir.keep_modes[0]
    r_fac, s_fac = _cg_factor_groups(ir, dense_ops)
    x = s_fac[mode]
    canon = ir.sparse_term[mode] + ir.rank_index
    # the fused kernel computes the Khatri-Rao gather ONCE and reuses it for
    # both halves — only valid when both halves share the same factor
    # objects (always true via planned_cg_matvec); without an ingest-time
    # cached bucket pattern (tracing), fall back to the composition
    shared = all(s_fac[d] is r_fac[d] for d in range(len(r_fac)) if d != mode)
    if path == "fused" and shared:
        buckets = st.row_buckets(mode, config.block_rows)
        if buckets is not None:
            res = kops.cg_matvec_bucketed(buckets, r_fac, x,
                                          num_rows=st.shape[mode])
            return ctx.psum_data(_reorder(res, canon, ir.out))
    if path == "fused":
        obs.counter_add("dispatch/fallback/cg_matvec_fused")
    if path in ("fused", "tttp_mttkrp"):
        partial = ctx.psum_model(core_tttp.multilinear_values(st, s_fac))
        z = st.with_values(st.values * partial)
        return ctx.psum_data(_reorder(sops.mttkrp(z, r_fac, mode), canon,
                                      ir.out))
    if path == "sliced":
        r2 = ir.size_of(ir.rank2_index)
        h2 = _sliced_h(r2)
        rs2 = r2 // h2
        acc = jnp.zeros((st.cap,), st.values.dtype)
        for h in range(h2):
            sl = [None if f is None else f[:, h * rs2:(h + 1) * rs2]
                  for f in s_fac]
            acc = acc + core_tttp.multilinear_values(st, sl)
        z = st.with_values(st.values * ctx.psum_model(acc))
        r1 = ir.rank_size
        h1 = _sliced_h(r1)
        rs1 = r1 // h1
        cols = [sops.mttkrp(
            z, [None if f is None else f[:, h * rs1:(h + 1) * rs1]
                for f in r_fac], mode) for h in range(h1)]
        res = jnp.concatenate(cols, axis=1) if h1 > 1 else cols[0]
        return ctx.psum_data(_reorder(res, canon, ir.out))
    raise ValueError(f"unknown CG_MATVEC path {path!r}")


def execute(ir: pir.ContractionIR, path: str, operands: Sequence,
            ctx: Optional[AxisCtx] = None,
            config: Optional[PlannerConfig] = None):
    """Run the contraction along ``path``. Operand list must match the IR;
    ``ctx`` supplies the mesh axes whose collectives dispatch applies (None
    or LOCAL ⇒ single-device semantics).

    With tracing enabled (``repro.obs``), each EAGER execution records a
    span plus a predicted-vs-measured plan entry: the §5.3 cost-model
    flop/traffic/comm prediction for this (IR, path) next to the fenced
    wall time — the persistent accounting that validates the cost model
    (DESIGN.md §11). Traced executions (inside jit) skip all of it."""
    if not (obs.enabled() and obs.trace_clean()):
        return _execute(ir, path, operands, ctx, config)
    kind = str(ir.kind)
    with obs.span(f"planner/{kind}/{path}", expr=ir.expr, nnz=ir.nnz,
                  rank=ir.rank_size) as sp:
        t0 = time.perf_counter()
        out = sp.fence(_execute(ir, path, operands, ctx, config))
        seconds = time.perf_counter() - t0
    from repro.planner import cost as pcost
    c = pcost.estimate(ir, path)
    obs.get_registry().record_plan(
        f"{ir.expr}|{path}|m{ir.nnz}|r{ir.rank_size}",
        kind, path, ir.expr,
        {"flops": c.flops, "mem": c.mem, "comm": c.comm,
         "seconds": c.seconds}, seconds)
    return out


def _execute(ir: pir.ContractionIR, path: str, operands: Sequence,
             ctx: Optional[AxisCtx], config: Optional[PlannerConfig]):
    ctx = ctx if ctx is not None else LOCAL
    config = config if config is not None else default_config()
    if ir.kind == pir.DENSE:
        return jnp.einsum(ir.expr, *operands)
    st, dense_ops = _split_operands(ir, operands)
    if ir.kind == pir.REDUCE:
        return _exec_reduce(ir, st, path, ctx)
    if ir.kind == pir.TTTP:
        return _exec_tttp(ir, st, dense_ops, path, ctx, config)
    if ir.kind == pir.TTM:
        return _exec_ttm(ir, st, dense_ops, path, ctx)
    if ir.kind == pir.MTTKRP:
        return _exec_mttkrp(ir, st, dense_ops, path, ctx, config)
    if ir.kind == pir.CG_MATVEC:
        return _exec_cg_matvec(ir, st, dense_ops, path, ctx, config)
    raise ValueError(f"unknown IR kind {ir.kind!r}")
