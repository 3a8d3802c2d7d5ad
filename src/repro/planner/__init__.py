"""Sparse einsum planner: cost-model-driven contraction paths with plan
caching and kernel dispatch (DESIGN.md §5).

Layering::

    ir.py        einsum IR — parse + classify into contraction families
    cost.py      paper §5.3 flop/memory formulas per candidate path
    plan.py      path enumeration, ranking, plan cache, autotuning
    tuner.py     measured kernel-tile autotuning + on-disk plan cache
    dispatch.py  lowering onto repro.sparse.ops / repro.kernels

``repro.core.api.einsum`` and ``api.TTTP`` are thin shims over
:func:`planned_einsum`; the completion solvers opt in through the
``path=`` overrides of :func:`planned_mttkrp` / :func:`planned_tttp`.
Each of :func:`planned_tttp`, :func:`planned_mttkrp` and
:func:`planned_cg_matvec` runs under the ``obs.scope`` of its kernel
family (``tttp``, ``mttkrp``, ``cg_matvec``), so every solver's compiled
operations name the family they belong to.
"""
from __future__ import annotations

import functools
from typing import List, Optional, Sequence, Tuple

import jax

from repro import obs
from repro.core.distributed import AxisCtx, LOCAL
from repro.core.sparse_tensor import SparseTensor
from repro.planner import config as _pconfig
from repro.planner.config import (DEFAULT_CONFIG, PlannerConfig,
                                  default_config, set_default_config)
from repro.planner.cost import PathCost, candidate_paths, estimate, rank_paths
from repro.planner.dispatch import execute
from repro.planner.ir import ContractionIR, DistInfo, build_ir
from repro.planner.plan import (Plan, clear_plan_cache, plan_cache_size,
                                plan_contraction)
from repro.planner.tuner import ensure_tuned

__all__ = [
    "ContractionIR", "DistInfo", "PathCost", "Plan", "PlannerConfig",
    "DEFAULT_CONFIG", "default_config", "set_default_config",
    "build_ir", "candidate_paths", "estimate", "rank_paths",
    "plan_contraction", "clear_plan_cache", "plan_cache_size",
    "execute", "ensure_tuned", "planned_einsum", "planned_mttkrp",
    "planned_tttp", "planned_cg_matvec", "planned_reduce",
    "mttkrp_fn", "tttp_fn",
]

# mode letters for synthesized expressions; 'z' is reserved for the kept
# rank, 'y' for the contracted rank of the Gram-matvec family
_MODE_LETTERS = "abcdefghij"
_RANK_LETTER = "z"
_RANK2_LETTER = "y"


def mttkrp_fn(path: Optional[str] = None):
    """The solvers' opt-in seam: ``None`` returns the direct kernel
    (``sparse.ops.mttkrp``, no planning overhead); a path string returns a
    drop-in pinned to that planner path. Same ``(st, factors, mode)``
    signature either way."""
    if path is None:
        from repro.sparse import ops as sops
        return sops.mttkrp
    return functools.partial(planned_mttkrp, path=path)


def tttp_fn(path: Optional[str] = None):
    """As :func:`mttkrp_fn` for TTTP: ``None`` → ``kernels.ops.tttp``,
    a path string → planner dispatch pinned to it."""
    if path is None:
        from repro.kernels import ops as kops
        return kops.tttp
    return functools.partial(planned_tttp, path=path)


def planned_einsum(expr: str, *operands, path: Optional[str] = None,
                   plan: Optional[Plan] = None, autotune: bool = False,
                   ctx: AxisCtx = LOCAL, rowsharded: bool = False,
                   config: Optional[PlannerConfig] = None):
    """Einsum through the planner; ``path=`` forces a candidate, ``plan=``
    bypasses planning entirely (the caller owns signature compatibility),
    ``ctx=`` names the mesh axes the call runs under (collectives applied
    inside dispatch, communication terms in the ranking — DESIGN.md §9)."""
    if plan is None:
        if not any(isinstance(op, SparseTensor) for op in operands):
            # pure-dense: nothing to plan — delegate to jnp.einsum, which
            # takes arrays only (lists/scalars are converted here)
            import jax.numpy as jnp
            return jnp.einsum(expr, *map(jnp.asarray, operands))
        plan = plan_contraction(expr, operands, path=path, autotune=autotune,
                                ctx=ctx, rowsharded=rowsharded, config=config)
    return plan.execute(operands)


def _synth_expr(ndim: int, factor_modes: Sequence[int], out: str) -> str:
    s_term = _MODE_LETTERS[:ndim]
    terms = [s_term] + [s_term[d] + _RANK_LETTER for d in factor_modes]
    return ",".join(terms) + "->" + out


def planned_mttkrp(st: SparseTensor, factors: Sequence[Optional[jax.Array]],
                   mode: int, path: Optional[str] = None,
                   autotune: bool = False, ctx: AxisCtx = LOCAL,
                   rowsharded: bool = False, h_slices: int = 1,
                   config: Optional[PlannerConfig] = None) -> jax.Array:
    """Classic MTTKRP onto ``mode`` via the planner (drop-in for
    ``repro.sparse.ops.mttkrp``). ``factors[mode]`` is ignored/None.
    ``rowsharded`` declares factor rows sharded over ``ctx``'s data axes
    (dispatches the gather/reduce-scatter path, H-sliced by ``h_slices``)."""
    present = [d for d in range(st.ndim) if d != mode and factors[d] is not None]
    out = _MODE_LETTERS[mode] + _RANK_LETTER
    expr = _synth_expr(st.ndim, present, out)
    ops = (st, *[factors[d] for d in present])
    if h_slices != 1:
        config = (config or _pconfig.default_config()).with_h_slices(h_slices)
    with obs.scope("mttkrp"):
        return planned_einsum(expr, *ops, path=path, autotune=autotune,
                              ctx=ctx, rowsharded=rowsharded, config=config)


def planned_reduce(st: SparseTensor, keep_modes: Tuple[int, ...],
                   path: Optional[str] = None,
                   ctx: AxisCtx = LOCAL) -> jax.Array:
    """Sparse mode-subset reduction via the planner (drop-in for
    ``SparseTensor.reduce_mode`` with psum(data) under ``ctx``)."""
    s_term = _MODE_LETTERS[:st.ndim]
    expr = s_term + "->" + "".join(s_term[d] for d in keep_modes)
    return planned_einsum(expr, st, path=path, ctx=ctx)


def planned_cg_matvec(weights: SparseTensor,
                      factors: Sequence[jax.Array], mode: int,
                      x: jax.Array, path: Optional[str] = None,
                      autotune: bool = False, ctx: AxisCtx = LOCAL,
                      config: Optional[PlannerConfig] = None) -> jax.Array:
    """Weighted Gram matvec (paper §2.2 + eq. 3) via the planner:

        y[i, r] = Σ_{n: i_mode(n)=i} ω_n (Π_{d≠mode} A_d[i_d, r]) ·
                  Σ_s x[i, s] Π_{d≠mode} A_d[i_d, s]

    ``weights.values`` holds ω_n (the Ω indicator for plain ALS, the loss
    curvature ℓ''(t_n, m_n) for the generalized Gauss-Newton solver).
    Candidate paths: ``fused`` (the single-pass ``kernels.ops
    .cg_matvec_bucketed``), ``tttp_mttkrp`` (eq.-3 composition), ``sliced``
    (H-sliced both halves), ``dense``. Regularization/damping is NOT
    included — callers add ``lam * x`` themselves."""
    nd = weights.ndim
    others = [d for d in range(nd) if d != mode]
    if any(factors[d] is None for d in others):
        raise ValueError("the Gram matvec needs a factor on every "
                         "non-target mode")
    s_term = _MODE_LETTERS[:nd]
    terms = ([s_term]
             + [s_term[d] + _RANK_LETTER for d in others]
             + [s_term[mode] + _RANK2_LETTER]
             + [s_term[d] + _RANK2_LETTER for d in others])
    expr = ",".join(terms) + "->" + s_term[mode] + _RANK_LETTER
    ops = (weights, *[factors[d] for d in others], x,
           *[factors[d] for d in others])
    with obs.scope("cg_matvec"):
        return planned_einsum(expr, *ops, path=path, autotune=autotune,
                              ctx=ctx, config=config)


def planned_tttp(st: SparseTensor, factors: Sequence[Optional[jax.Array]],
                 path: Optional[str] = None, autotune: bool = False,
                 ctx: AxisCtx = LOCAL, rowsharded: bool = False,
                 h_slices: int = 1,
                 config: Optional[PlannerConfig] = None) -> SparseTensor:
    """TTTP via the planner (drop-in for ``repro.core.tttp.tttp``): accepts
    None entries and vector factors, per the paper's Listing 3 surface."""
    fs: List[Optional[jax.Array]] = [
        None if f is None else (f[:, None] if f.ndim == 1 else f)
        for f in factors]
    present = [d for d in range(st.ndim) if fs[d] is not None]
    if not present:
        raise ValueError("TTTP requires at least one factor")
    s_term = _MODE_LETTERS[:st.ndim]
    expr = _synth_expr(st.ndim, present, s_term)
    ops = (st, *[fs[d] for d in present])
    if h_slices != 1:
        config = (config or _pconfig.default_config()).with_h_slices(h_slices)
    with obs.scope("tttp"):
        return planned_einsum(expr, *ops, path=path, autotune=autotune,
                              ctx=ctx, rowsharded=rowsharded, config=config)
