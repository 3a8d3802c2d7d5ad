"""ALS for tensor completion with implicit batched conjugate gradient —
the paper's new algorithm (§2.2), plus the explicit (Gram-forming) baseline
it improves upon (Karlsson/Smith-style).

Implicit CG: for each mode, solve the I independent R×R SPD systems
    (G^(i) + λI) u_i = b_i,   b = MTTKRP(T, factors)
without ever forming G^(i). The batched matvec (paper eq. 3) is

    Y = MTTKRP( TTTP(Ω, [..., X at mode, ...]), factors ) + λX

i.e. one TTTP + one MTTKRP per CG iteration — O(mR) each. CG touches rows
only through the matvec, so all I systems run batched in lockstep; converged
rows are frozen by masking. Everything is ctx-parameterized: the identical
code runs single-device or under shard_map (DESIGN.md §4).
"""
from __future__ import annotations

import functools
from typing import List, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp

from repro import obs
from repro.core.distributed import (AxisCtx, LOCAL, mttkrp_ctx, rowdot_ctx,
                                    tttp_ctx)
from repro.core.sparse_tensor import SparseTensor


def gram_matvec(omega: SparseTensor, factors: Sequence[jax.Array], mode: int,
                x: jax.Array, lam: float, ctx: AxisCtx = LOCAL,
                h_slices: int = 1,
                mttkrp_path: Optional[str] = None,
                matvec_path: Optional[str] = None) -> jax.Array:
    """(G_ω + λI) x via implicit TTTP+MTTKRP (paper eq. 3).

    ``omega.values`` are the per-entry weights ω_n — the Ω indicator for
    plain ALS, the loss curvature ℓ'' for the generalized Gauss-Newton
    solver (``completion.gauss_newton``).

    ``h_slices > 1`` applies the paper's H-slicing schedule to BOTH halves:
    the (m, R) Khatri-Rao intermediates are never materialized wider than
    R/H columns, bounding transient memory at Θ(m·R/H) (paper §3.2).
    ``mttkrp_path`` opts the MTTKRP half into planner dispatch (DESIGN.md §5).
    ``matvec_path`` routes the WHOLE weighted matvec through the planner's
    ``cg_matvec`` family instead — ``"fused"`` (single-pass
    ``kernels.ops.cg_matvec_bucketed``), ``"tttp_mttkrp"``, ``"sliced"``,
    ``"dense"``, or ``"auto"`` (§5.3 cost model decides). Works under any
    ctx: dispatch inserts the psum(model) between the halves and the
    psum(data) on the output (under a model axis the fused/dense candidates
    are excluded — the intermediate psum cannot be fused)."""
    if matvec_path is not None:
        from repro.planner import planned_cg_matvec
        path = None if matvec_path == "auto" else matvec_path
        if path in ("fused", "dense") and ctx.model is not None:
            # neither candidate can express the inter-half psum(model);
            # degrade to the cost-model choice rather than raising (the
            # fused path's local-fallback story, applied to the mesh)
            path = None
        y = planned_cg_matvec(omega, list(factors), mode, x, path=path,
                              ctx=ctx)
        return y + lam * x
    fs = list(factors)
    fs[mode] = x
    if h_slices <= 1:
        z = tttp_ctx(omega, fs, ctx)        # z_n = Σ_s Π a_ds · x_is  (TTTP)
        fs[mode] = None
        y = mttkrp_ctx(z, fs, mode, ctx, path=mttkrp_path)
        return y + lam * x
    from repro.core.tttp import multilinear_values
    r = x.shape[1]
    rs = -(-r // h_slices)
    with obs.scope("tttp"):
        acc = jnp.zeros((omega.cap,), omega.values.dtype)
        for h in range(h_slices):
            sl = [None if f is None else f[:, h * rs:(h + 1) * rs]
                  for f in fs]
            acc = acc + multilinear_values(omega, sl)
        z = omega.with_values(omega.values * ctx.psum_model(acc))
    fs[mode] = None
    from repro.planner import mttkrp_fn
    mv_kernel = mttkrp_fn(mttkrp_path)
    with obs.scope("mttkrp"):
        cols = []
        for h in range(h_slices):
            sl = [None if f is None else f[:, h * rs:(h + 1) * rs]
                  for f in fs]
            cols.append(mv_kernel(z, sl, mode))
        y = ctx.psum_data(jnp.concatenate(cols, axis=1)[:, :r])
    return y + lam * x


def batched_pcg(matvec, b: jax.Array, x0: jax.Array, precond=None,
                tol: float = 1e-4, max_iters: int = 32,
                ctx: AxisCtx = LOCAL):
    """Preconditioned batched-rows CG on SPD systems; rows converge
    independently (converged rows are frozen by masking).

    ``precond`` is M⁻¹ applied elementwise over the (rows, R) batch —
    block-Jacobi when M is each row's block diagonal; ``None`` is the
    identity (plain CG). Stops (whole batch) when every row residual²
    ≤ tol²·‖b_row‖², or at max_iters (≤ R guarantees exact solve modulo
    roundoff, §2.2). Returns ``(x, steps)``: the solution and the
    ``while_loop`` steps taken (int32).

    Each ``matvec`` call runs under ``obs.scope("matvec")``, the dense row
    updates and row dots under ``obs.scope("cg_update")``."""
    if precond is None:
        precond = lambda v: v
    with obs.scope("cg_update"):
        bnorm2 = rowdot_ctx(b, b, ctx)
        thresh = (tol ** 2) * jnp.maximum(bnorm2, 1e-30)
    with obs.scope("matvec"):
        ax0 = matvec(x0)
    with obs.scope("cg_update"):
        r0 = b - ax0
        z0 = precond(r0)
        init = (jnp.int32(0), x0, r0, z0, rowdot_ctx(r0, z0, ctx),
                rowdot_ctx(r0, r0, ctx))

    def cond(state):
        i, x, r, p, rz, rs = state
        with obs.scope("cg_update"):
            return (i < max_iters) & jnp.any(rs > thresh)

    def body(state):
        i, x, r, p, rz, rs = state
        with obs.scope("matvec"):
            ap = matvec(p)
        with obs.scope("cg_update"):
            pap = rowdot_ctx(p, ap, ctx)
            active = rs > thresh
            alpha = jnp.where(active, rz / jnp.where(pap > 0, pap, 1.0), 0.0)
            x = x + alpha[:, None] * p
            r = r - alpha[:, None] * ap
            z = precond(r)
            rz_new = rowdot_ctx(r, z, ctx)
            beta = jnp.where(active, rz_new / jnp.where(rz != 0, rz, 1.0),
                             0.0)
            p = z + beta[:, None] * p
            return i + 1, x, r, p, rz_new, rowdot_ctx(r, r, ctx)

    iters, x, r, p, rz, rs = jax.lax.while_loop(cond, body, init)
    return x, iters


def batched_cg(matvec, b: jax.Array, x0: jax.Array, tol: float = 1e-4,
               max_iters: int = 32, ctx: AxisCtx = LOCAL):
    """Unpreconditioned :func:`batched_pcg` (z = r makes rz ≡ rs)."""
    return batched_pcg(matvec, b, x0, precond=None, tol=tol,
                       max_iters=max_iters, ctx=ctx)


# ---------------------------------------------------------------------------
# The Gram operator of one mode in row-slab form
#
# Mode d's nonzeros, sorted by their mode-d row, are cut into slabs of K
# slots; a row of n nonzeros fills ceil(n / K) slabs of its own. Each slot
# holds its nonzero's Khatri-Rao row a_n = Π_{e≠d} A_e[i_e(n)], with the rank
# on a major axis, so no rank-R row is ever padded to the lanes. K is read
# from the mode's static shape (slab_width): SLAB where rows are long, the
# slots then on the lane axis; a narrower power of two where they are short,
# the slabs then on the lanes and a slab's K slots on the sublanes. Built
# once per mode update, the operator turns each CG matvec into two streams
# over the slabs and a sorted scatter of one row per slab, instead of
# gathers and a scatter over every nonzero.
# ---------------------------------------------------------------------------

SLAB = 128            # slots per slab: one lane row of a TPU vector register
NARROW = 8            # the narrowest slab: one float32 sublane tile
BUILD_SLOTS = 1 << 19  # slots whose factor rows one build step gathers


class GramSlabs(NamedTuple):
    """Mode ``d``'s Gram operator in row-slab form (:func:`gram_slabs`),
    its slabs of ``K`` slots laid out by width: at ``K == SLAB`` slab
    ``c * P + s`` is ``[c, ..., s, :]``, narrower ``[c, ..., :, s]``."""
    z: jax.Array     # (C, R, P, K) or (C, R, K, P) a_n
    w: jax.Array     # (C, P, K) or (C, K, P) ω_n, the Gram's; 0 if unused
    v: jax.Array     # as w: t_n, the right-hand side's; 0 if unused
    row: jax.Array   # (C * P,) int32 row of each slab, sorted; I_d if unused

    @property
    def width(self) -> int:
        """``K``, the slots of one slab."""
        return self.w.size // self.row.size

    @property
    def slot_axis(self) -> int:
        """The axis of ``z`` that holds a slab's slots."""
        return -1 if self.width == SLAB else -2


def slab_width(omega: SparseTensor, mode: int) -> int:
    """Slots per slab for ``mode``: ``SLAB`` where its rows hold at least
    that many nonzeros on average (static: ``cap / I_d``), else the least
    power of two, from ``NARROW``, that is at least that average."""
    k, n_rows = NARROW, omega.shape[mode]
    while k < SLAB and k * n_rows < omega.cap:
        k *= 2
    return k


def slab_mode(omega: SparseTensor, mode: int, ctx: AxisCtx = LOCAL,
              h_slices: int = 1, mttkrp_path: Optional[str] = None) -> bool:
    """Whether ALS solves ``mode`` on the row-slab operator: replicated
    factor columns, no H-slicing, no planner path asked for, and at least
    one nonzero a row on average (static: ``cap >= I_d``). A sparser mode
    would spend most slots on padding (a reserve of ``K - 1`` a row); it
    keeps the COO matvec."""
    return (ctx.model is None and h_slices == 1 and mttkrp_path is None
            and omega.cap >= omega.shape[mode])


def _row_counts(rows: jax.Array, n_rows: int,
                chunk: int = 1 << 18) -> jax.Array:
    """How many of ``rows`` fall on each of ``n_rows`` rows (others are
    ignored): a histogram as int8 one-hot matmuls (row = 128 hi + lo), with
    no scatter and no sort."""
    hi_n = -(-n_rows // 128)
    chunk = min(chunk, -(-rows.size // 128) * 128)
    rows = jnp.pad(rows, (0, -rows.size % chunk),
                   constant_values=-1).reshape(-1, chunk)

    def step(acc, r):                                # one-hots (·, chunk)
        lo = (r % 128 == jnp.arange(128)[:, None]).astype(jnp.int8)
        hi = (r // 128 == jnp.arange(hi_n)[:, None]).astype(jnp.int8)
        return acc + jax.lax.dot_general(
            hi, lo, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.int32), None

    acc, _ = jax.lax.scan(step, jnp.zeros((hi_n, 128), jnp.int32), rows)
    return acc.reshape(-1)[:n_rows]


def gram_slabs(st: SparseTensor, omega: SparseTensor,
               factors: Sequence[jax.Array], mode: int) -> GramSlabs:
    """Build mode ``mode``'s :class:`GramSlabs` from ``omega``'s pattern,
    which ``st`` shares (``omega`` is ``st.with_values(...)``, as ALS takes
    them).

    Slabs are ``K = slab_width(omega, mode)`` slots wide. Each row ``i`` is
    given ``-n_i mod K`` pad entries (out of ``K - 1`` it holds in reserve,
    the rest going to the sentinel row ``I_d`` with the invalid entries),
    so that once nonzeros and pads are sorted by row every row's run fills
    whole slabs and the sorted order *is* the slab order: ``cap + I_d (K -
    1)`` entries, so no shape depends on the data. The sort key carries as
    many of the other coordinates in its low bits as fit in 31 bits, and
    the sort carries the rest, the weights and the values along: a gather
    from an array of ``cap`` entries in a random order is far slower on a
    TPU than the sort, and each operand less saves the TPU compiler close
    to a minute. The Khatri-Rao rows are then gathered from the factors
    ``BUILD_SLOTS`` slots a step, so the lane-padded rows never span all
    the slots at once, and a step past the last row's slabs is skipped.
    Below ``SLAB`` each step's slots are taken slot-major, so that its
    slabs land on the lanes."""
    n_rows, cap, k = omega.shape[mode], omega.cap, slab_width(omega, mode)
    others = [e for e in range(omega.ndim) if e != mode]
    rank, dtype = factors[others[0]].shape[1], factors[others[0]].dtype
    row = jnp.where(omega.valid, omega.indices[:, mode], n_rows)
    pads = -_row_counts(row, n_rows) % k                            # (I_d,)
    pad_row = jnp.where(jnp.arange(k - 1) < pads[:, None],
                        jnp.arange(n_rows, dtype=row.dtype)[:, None], n_rows)
    entries = cap + n_rows * (k - 1)
    per = max(1, min(-(-entries // k), BUILD_SLOTS // k))
    chunks = -(-entries // (per * k))
    fill = chunks * per * k - cap

    def padded(a, value=0):
        return jnp.concatenate([a, jnp.full((fill,), value, a.dtype)])

    rows = padded(row, n_rows).at[cap:cap + pad_row.size].set(
        pad_row.reshape(-1))
    # the row, then the narrowest other coordinates that fit, in one key
    keys, packed = rows, []
    for e in sorted(others, key=lambda e: omega.shape[e]):
        b = max(1, (omega.shape[e] - 1).bit_length())
        if n_rows.bit_length() + sum(n for _, n in packed) + b > 31:
            break
        keys = (keys << b) | padded(omega.indices[:, e])
        packed.append((e, b))
    rest = [e for e in others if e not in dict(packed)]
    w = jnp.where(omega.valid, omega.values, 0)
    v = jnp.where(st.valid, st.values, 0).astype(w.dtype)
    keys, *rest_cols, w, v = jax.lax.sort(
        (keys, *[padded(omega.indices[:, e]) for e in rest], padded(w),
         padded(v)), num_keys=1)
    cols = dict(zip(rest, rest_cols))
    for e, b in reversed(packed):
        cols[e], keys = keys & ((1 << b) - 1), keys >> b

    step = (per, k) if k == SLAB else (k, per)

    def laid(a):                               # (chunks * P * K,) -> layout
        a = a.reshape(chunks, per, k)
        return a if k == SLAB else a.transpose(0, 2, 1)

    cols = jnp.stack([laid(cols[e]).reshape(chunks, -1) for e in others], 1)

    def build(idx):                             # (E, P*K) -> (R, *step)
        prod = None
        for e, col in zip(others, idx):
            got = factors[e][col]
            prod = got if prod is None else prod * got
        return prod.T.reshape(-1, *step)

    # a step past the last row's slabs holds only the sentinel row
    z = jnp.stack([jax.lax.cond(keys[c * per * k] < n_rows, build,
                                lambda idx: jnp.zeros((rank, *step), dtype),
                                cols[c]) for c in range(chunks)])
    return GramSlabs(z, laid(w), laid(v), keys.reshape(-1, k)[:, 0])


def _slab_rows(ops: GramSlabs, y: jax.Array, n_rows: int,
               ctx: AxisCtx) -> jax.Array:
    """Sum ``(C, R, P)`` per-slab rows into their rows: a sorted scatter of
    one row per slab, then the psum over the data axes."""
    y = y.transpose(0, 2, 1).reshape(ops.row.shape[0], -1)
    return ctx.psum_data(jax.ops.segment_sum(
        y, ops.row, num_segments=n_rows, indices_are_sorted=True))


def slab_matvec(ops: GramSlabs, x: jax.Array, lam: float,
                ctx: AxisCtx = LOCAL) -> jax.Array:
    """``(G_ω + λI) x`` on the row-slab operator: what :func:`gram_matvec`
    computes, with one gather of ``x`` per slab and no pass over the
    nonzeros' coordinates."""
    c, r = ops.z.shape[:2]
    with obs.scope("tttp"):
        xs = jnp.take(x, ops.row, axis=0, mode="clip")
        xs = xs.reshape(c, -1, r).transpose(0, 2, 1)                # (C, R, P)
        t = ops.w * jnp.sum(ops.z * jnp.expand_dims(xs, ops.slot_axis),
                            axis=1)                                 # as ops.w
    with obs.scope("mttkrp"):
        y = _slab_rows(ops, jnp.sum(ops.z * t[:, None], axis=ops.slot_axis),
                       x.shape[0], ctx)
    return y + lam * x


def slab_rhs(ops: GramSlabs, n_rows: int, ctx: AxisCtx = LOCAL) -> jax.Array:
    """The right-hand side ``MTTKRP(st)`` on the row-slab operator."""
    return _slab_rows(ops, jnp.sum(ops.z * ops.v[:, None],
                                   axis=ops.slot_axis), n_rows, ctx)


def als_update_mode(st: SparseTensor, omega: SparseTensor,
                    factors: List[jax.Array], mode: int, lam: float,
                    cg_tol: float = 1e-4, cg_iters: int = 32,
                    ctx: AxisCtx = LOCAL, h_slices: int = 1,
                    mttkrp_path: Optional[str] = None):
    """One ALS factor update by implicit CG; returns ``(factor, CG
    steps)``. The right-hand-side MTTKRP runs under ``obs.scope("rhs")``.
    ``mttkrp_path`` opts the MTTKRP contractions into planner dispatch
    (repro.planner).

    Where :func:`slab_mode` holds, the mode's Gram operator is built once,
    under ``obs.scope("gram_build")``, in row-slab form (:func:`gram_slabs`)
    and both the right-hand side and every CG matvec apply it; otherwise
    the COO :func:`gram_matvec` runs. Each traced update bumps the counter
    ``als/gram/slab`` or ``als/gram/coo``; a slab update also sets the
    gauges ``als/gram/mode_<d>/slots``, ``.../cap`` and ``.../width``
    (``K``, the slots of one slab), a COO update
    ``als/gram/mode_<d>/rows``, ``.../cap`` and ``.../lanes`` (the lanes
    each gathered or scattered rank-R row occupies, R rounded up to a
    whole number of ``SLAB``-lane rows)."""
    if slab_mode(omega, mode, ctx, h_slices, mttkrp_path):
        obs.counter_add("als/gram/slab")
        with obs.scope("gram_build"):
            ops = gram_slabs(st, omega, factors, mode)
        obs.gauge_set(f"als/gram/mode_{mode}/slots", ops.w.size)
        obs.gauge_set(f"als/gram/mode_{mode}/cap", omega.cap)
        obs.gauge_set(f"als/gram/mode_{mode}/width", ops.width)
        with obs.scope("rhs"):
            b = slab_rhs(ops, omega.shape[mode], ctx)
        mv = functools.partial(slab_matvec, ops, lam=lam, ctx=ctx)
    else:
        obs.counter_add("als/gram/coo")
        obs.gauge_set(f"als/gram/mode_{mode}/rows", omega.shape[mode])
        obs.gauge_set(f"als/gram/mode_{mode}/cap", omega.cap)
        obs.gauge_set(f"als/gram/mode_{mode}/lanes",
                      -(-factors[mode].shape[1] // SLAB) * SLAB)
        fs = list(factors)
        fs[mode] = None
        with obs.scope("rhs"):
            b = mttkrp_ctx(st, fs, mode, ctx, path=mttkrp_path)
        mv = functools.partial(gram_matvec, omega, factors, mode, lam=lam,
                               ctx=ctx, h_slices=h_slices,
                               mttkrp_path=mttkrp_path)
    return batched_cg(mv, b, factors[mode], tol=cg_tol, max_iters=cg_iters,
                      ctx=ctx)


def als_sweep_stats(st: SparseTensor, omega: SparseTensor,
                    factors: Sequence[jax.Array], lam: float,
                    cg_tol: float = 1e-4, cg_iters: int = 32,
                    ctx: AxisCtx = LOCAL, h_slices: int = 1,
                    mttkrp_path: Optional[str] = None):
    """:func:`als_sweep` that also returns its solver counter:
    ``(factors, cg_steps)``, with ``cg_steps`` an ``int32[N]`` of the CG
    steps each mode's update ran. Mode ``d``'s update runs under
    ``obs.scope(f"mode_{d}")``."""
    fs = list(factors)
    steps = []
    for d in range(st.ndim):
        with obs.scope(f"mode_{d}"):
            fs[d], n = als_update_mode(st, omega, fs, d, lam, cg_tol,
                                       cg_iters, ctx, h_slices,
                                       mttkrp_path=mttkrp_path)
        steps.append(n)
    return fs, jnp.stack(steps)


def als_sweep(st: SparseTensor, omega: SparseTensor,
              factors: Sequence[jax.Array], lam: float,
              cg_tol: float = 1e-4, cg_iters: int = 32,
              ctx: AxisCtx = LOCAL, h_slices: int = 1,
              mttkrp_path: Optional[str] = None) -> List[jax.Array]:
    """Full ALS sweep (all modes, in order) — paper Algorithm of §2.2."""
    return als_sweep_stats(st, omega, factors, lam, cg_tol, cg_iters, ctx,
                           h_slices, mttkrp_path)[0]


# ---------------------------------------------------------------------------
# Explicit baseline: form all G^(i), solve with batched direct solves.
# O(mR²) work, O(IR²) memory — the bottleneck the implicit method removes.
# ---------------------------------------------------------------------------

def als_update_mode_explicit(st: SparseTensor, factors: List[jax.Array],
                             mode: int, lam: float,
                             ctx: AxisCtx = LOCAL) -> jax.Array:
    others = [d for d in range(st.ndim) if d != mode]
    kr = None
    for d in others:
        rows = factors[d][st.indices[:, d]]
        kr = rows if kr is None else kr * rows                  # (cap, R)
    kr = kr * st.mask[:, None]
    rows = st.indices[:, mode]
    n_rows = st.shape[mode]
    # G^(i) = Σ_n kr_n kr_nᵀ  — the O(mR²) contraction
    outer = kr[:, :, None] * kr[:, None, :]
    gram = jax.ops.segment_sum(outer, rows, num_segments=n_rows)
    gram = ctx.psum_data(gram)
    b = jax.ops.segment_sum((st.values * st.mask)[:, None] * kr, rows,
                            num_segments=n_rows)
    b = ctx.psum_data(b)
    r = kr.shape[1]
    gram = gram + lam * jnp.eye(r, dtype=gram.dtype)
    return jax.vmap(jnp.linalg.solve)(gram, b)


def als_sweep_explicit(st: SparseTensor, factors: Sequence[jax.Array],
                       lam: float, ctx: AxisCtx = LOCAL) -> List[jax.Array]:
    fs = list(factors)
    for d in range(st.ndim):
        fs[d] = als_update_mode_explicit(st, fs, d, lam, ctx)
    return fs
