"""SPMD pass 1 — static sharding propagation over the planner IR
(DESIGN.md §15.1).

An abstract interpreter over jaxprs that assigns every intermediate a
*replication state* per mesh axis and certifies that each candidate path of
every planner family leaves no partial sum unreduced. The state lattice,
per (value, mesh axis):

* ``("rep",)``        — replicated: every device holds the same value.
* ``("shard", d)``    — device-distinct along dimension ``d`` (row/column
  ownership; ``d=None`` when the owning dimension is unknown). A shard is
  *correct* per device — it must never be psum'd.
* ``("part",)``       — partial sum: the true value is the psum over the
  axis. Sticky through arithmetic; only a psum (or reduce-scatter)
  discharges it.
* ``("over",)``       — over-reduced: a replicated value was psum'd again
  (the result is ``axis_size ×`` the intended value).

Transfer rules: collectives move between states (psum: part→rep;
all_gather: shard→rep; psum_scatter: part→shard); ``reduce_sum`` /
``dot_general`` contraction of a sharded dimension yields ``part``;
``gather`` with sharded indices yields row-sharded gathers, while a gather
that resolves global coordinates against a ROWS-tagged shard (a rowsharded
factor) is flagged (``SP004`` — the all_gather is missing; owner-aligned
gathers within a device's own nnz shard are legal local moves);
``scatter-add`` of device-distinct updates yields ``part``.
Control flow (``while``/``scan``) is handled by monotone fixpoint over the
carry, and a collective under a device-varying predicate is the classic
SPMD deadlock (``SP102``).

Findings:

* ``SP001`` partial-sum escape — an output is ``part``: a psum is missing.
* ``SP002`` redundant psum     — a replicated value was psum'd (``over``),
  or an over-reduced value escapes.
* ``SP003`` wrong replication state — a device-distinct shard was psum'd,
  or a shard escapes from a family whose output must be replicated.
* ``SP004`` sharded-dim gather — indexing into a dimension whose rows live
  on other devices (missing all_gather / rowsharded path).
* ``SP000`` analysis error     — a case/path failed to trace at all.

The exhaustive sweep (``run``/``check_cases``) walks the same
``contracts.iter_cases`` grid as the aval-agreement pass — all seven IR
families × orders 3–5 × local + every distributed variant × every candidate
path — and is exposed online as ``plan_contraction(..., validate_spmd=True)``
via :func:`certify_plan`. ``set_fault`` plants the two seeded defects the
CI tripwires prove the detector catches (``missing-psum``/``double-psum``).
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, List, Optional, Sequence, Tuple

from repro.analysis.lint import Finding

REP = ("rep",)
PART = ("part",)
OVER = ("over",)

# the "rows" tag marks a shard whose owning dimension is a GLOBALLY-indexed
# row space split across devices (a rowsharded factor): gathering into it
# with global coordinates is the missing-all_gather bug (SP004). Untagged
# shards are owner-aligned device-local data (the nnz shards of a sparse
# tensor), where intra-shard gathers/permutations are legal local moves.
ROWS = "rows"


def shard(dim: Optional[int] = None, tag: Optional[str] = None) -> Tuple:
    return ("shard", dim) if tag is None else ("shard", dim, tag)


def _shard_tag(v: Tuple) -> Optional[str]:
    return v[2] if len(v) > 2 else None


State = Tuple            # one of REP / PART / OVER / ("shard", d)
AxisStates = Dict[str, State]   # per mesh axis


class SpmdContractError(RuntimeError):
    """A candidate path's collective schedule is unsound (see findings)."""


# deliberate-fault hook (CI tripwire): "missing-psum" turns the AxisCtx
# psums into identity; "double-psum" applies each twice. The sweep MUST
# then fail with SP001 / SP002 respectively — proving the detector fires.
_FAULT: Optional[str] = None

FAULTS = ("missing-psum", "double-psum")


def set_fault(mode: Optional[str]) -> None:
    global _FAULT
    if mode is not None and mode not in FAULTS:
        raise ValueError(f"unknown fault {mode!r}; choose from {FAULTS}")
    _FAULT = mode


class _FaultCtx:
    """Duck-typed AxisCtx wrapper planting a seeded collective bug."""

    def __init__(self, inner, mode: str):
        self._inner, self._mode = inner, mode

    @property
    def data(self):
        return self._inner.data

    @property
    def model(self):
        return self._inner.model

    def data_size(self):
        return self._inner.data_size()

    def model_size(self):
        return self._inner.model_size()

    def model_index(self):
        return self._inner.model_index()

    def _apply(self, psum, x):
        if self._mode == "missing-psum":
            return x
        y = psum(x)
        return psum(y) if self._mode == "double-psum" else y

    def psum_data(self, x):
        return self._apply(self._inner.psum_data, x)

    def psum_model(self, x):
        return self._apply(self._inner.psum_model, x)


def _src(eqn) -> str:
    """Best-effort `file:line` of the traced call site, for messages."""
    try:
        from jax._src import source_info_util
        frame = source_info_util.user_frame(eqn.source_info)
        if frame is not None:
            return f" ({os.path.basename(frame.file_name)}:{frame.start_line})"
    except Exception:
        pass
    return ""


def _axis_names(value) -> Tuple[str, ...]:
    if value is None:
        return ()
    if isinstance(value, (tuple, list)):
        return tuple(v for v in value if isinstance(v, str))
    return (value,) if isinstance(value, str) else ()


# ---------------------------------------------------------------------------
# the abstract interpreter
# ---------------------------------------------------------------------------

class _Interp:
    def __init__(self, axes: Sequence[str], label: str):
        self.axes = tuple(axes)
        self.label = label
        self.findings: List[Finding] = []
        self.notes: List[str] = []

    def _finding(self, rule: str, msg: str, eqn=None) -> None:
        where = _src(eqn) if eqn is not None else ""
        self.findings.append(Finding(
            "spmd", 0, 0, rule, f"[{self.label}] {msg}{where}"))

    def _note(self, msg: str) -> None:
        self.notes.append(f"[{self.label}] {msg}")

    def _rep(self) -> AxisStates:
        return {ax: REP for ax in self.axes}

    # -- jaxpr walk ---------------------------------------------------------
    def run(self, jaxpr, in_states: Sequence[AxisStates],
            const_states: Optional[Sequence[AxisStates]] = None
            ) -> List[AxisStates]:
        import jax.extend.core
        env: Dict = {}

        def read(atom) -> AxisStates:
            if isinstance(atom, jax.extend.core.Literal):
                return self._rep()
            return env.get(atom, self._rep())

        def write(var, st: AxisStates) -> None:
            env[var] = st

        for cv in jaxpr.constvars:
            write(cv, self._rep())
        if const_states is not None:
            for cv, st in zip(jaxpr.constvars, const_states):
                write(cv, st)
        for iv, st in zip(jaxpr.invars, in_states):
            write(iv, {ax: st.get(ax, REP) for ax in self.axes})

        for eqn in jaxpr.eqns:
            ins = [read(a) for a in eqn.invars]
            for ov, st in zip(eqn.outvars, self._eqn(eqn, ins)):
                write(ov, st)
        return [read(a) for a in jaxpr.outvars]

    # -- one equation -------------------------------------------------------
    def _eqn(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        prim = eqn.primitive.name
        if prim in ("psum", "pmax", "pmin", "pmean"):
            return self._psum(eqn, ins)
        if prim == "all_gather":
            return self._all_gather(eqn, ins)
        if prim in ("reduce_scatter", "psum_scatter"):
            return self._psum_scatter(eqn, ins)
        if prim == "ppermute":
            return [dict(ins[0])]
        if prim == "axis_index":
            out = self._rep()
            for ax in _axis_names(eqn.params.get("axis_name")):
                if ax in self.axes:
                    out[ax] = shard(None)
            return [out]
        if prim in ("while", "scan"):
            return self._loop(eqn, ins)
        if prim == "cond":
            return self._cond(eqn, ins)
        sub = self._sub_jaxpr(eqn)
        if sub is not None and len(sub.invars) == len(ins):
            return [dict(s) for s in self.run(sub, ins)]
        if prim == "pallas_call" or sub is not None:
            # opaque body: propagate conservatively, never drop a `part`
            self._note(f"conservative state through `{prim}`")
            return [self._join_all(ins) for _ in eqn.outvars]
        return self._combine(eqn, ins)

    @staticmethod
    def _sub_jaxpr(eqn):
        for key in ("jaxpr", "call_jaxpr", "fun_jaxpr"):
            cj = eqn.params.get(key)
            if cj is None:
                continue
            return cj.jaxpr if hasattr(cj, "jaxpr") else cj
        return None

    def _join_all(self, ins: List[AxisStates]) -> AxisStates:
        out = {}
        for ax in self.axes:
            vals = [s.get(ax, REP) for s in ins]
            if any(v == OVER for v in vals):
                out[ax] = OVER
            elif any(v == PART for v in vals):
                out[ax] = PART
            elif any(v[0] == "shard" for v in vals):
                pairs = {(v[1], _shard_tag(v)) for v in vals
                         if v[0] == "shard"}
                if len(pairs) == 1:
                    d, tag = pairs.pop()
                    out[ax] = shard(d, tag)
                else:
                    out[ax] = shard(None)
            else:
                out[ax] = REP
        return out

    # -- collectives --------------------------------------------------------
    def _psum(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        named = [a for a in eqn.params.get("axes", ())
                 if isinstance(a, str)]
        outs = []
        for i, st in enumerate(ins):
            out = dict(st)
            for ax in named:
                if ax not in self.axes:
                    continue
                cur = st.get(ax, REP)
                if cur == PART:
                    out[ax] = REP
                elif cur == OVER:
                    out[ax] = OVER
                elif cur[0] == "shard":
                    self._finding(
                        "SP003",
                        f"psum over axis {ax!r} of a device-distinct "
                        f"sharded value — shards are per-device results, "
                        f"not partial sums; summing them mixes rows",
                        eqn)
                    out[ax] = REP
                else:
                    self._finding(
                        "SP002",
                        f"redundant psum over axis {ax!r}: the operand is "
                        f"already replicated, so the result is "
                        f"axis_size × the intended value", eqn)
                    out[ax] = OVER
            outs.append(out)
        return outs

    def _all_gather(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        p = eqn.params
        names = _axis_names(p.get("axis_name"))
        gdim = int(p.get("all_gather_dimension", 0))
        tiled = bool(p.get("tiled", False))
        st = ins[0]
        out: AxisStates = {}
        for ax in self.axes:
            cur = st.get(ax, REP)
            if ax in names:
                out[ax] = PART if cur == PART else (
                    OVER if cur == OVER else REP)
            elif cur[0] == "shard" and cur[1] is not None and not tiled:
                # a new stacked dimension is inserted at gdim
                out[ax] = shard(cur[1] + 1 if cur[1] >= gdim else cur[1])
            else:
                out[ax] = cur
        return [out]

    def _psum_scatter(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        p = eqn.params
        names = _axis_names(p.get("axis_name"))
        sdim = int(p.get("scatter_dimension", 0))
        tiled = bool(p.get("tiled", False))
        st = ins[0]
        out: AxisStates = {}
        for ax in self.axes:
            cur = st.get(ax, REP)
            if ax in names:
                if cur == PART:
                    out[ax] = shard(sdim if tiled else None)
                elif cur == REP:
                    self._finding(
                        "SP002",
                        f"psum_scatter over axis {ax!r} of a replicated "
                        f"value — each shard is axis_size × the slice", eqn)
                    out[ax] = OVER
                elif cur[0] == "shard":
                    self._finding(
                        "SP003",
                        f"psum_scatter over axis {ax!r} of a device-"
                        f"distinct shard mixes unrelated rows", eqn)
                    out[ax] = shard(None)
                else:
                    out[ax] = cur
            elif cur[0] == "shard" and cur[1] is not None and not tiled:
                out[ax] = shard(cur[1] - 1 if cur[1] > sdim else
                                (None if cur[1] == sdim else cur[1]))
            else:
                out[ax] = cur
        return [out]

    # -- structured control flow -------------------------------------------
    def _contains_collective(self, jaxpr) -> bool:
        for eqn in jaxpr.eqns:
            if eqn.primitive.name in ("psum", "pmax", "pmin", "pmean",
                                      "all_gather", "reduce_scatter",
                                      "psum_scatter", "all_to_all",
                                      "ppermute"):
                return True
            sub = self._sub_jaxpr(eqn)
            if sub is not None and self._contains_collective(sub):
                return True
            for br in eqn.params.get("branches", ()):
                if self._contains_collective(br.jaxpr):
                    return True
        return False

    @staticmethod
    def _varying(st: AxisStates) -> bool:
        return any(v != REP for v in st.values())

    def _join(self, a: AxisStates, b: AxisStates) -> AxisStates:
        return self._join_all([a, b])

    def _loop(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        p = eqn.params
        if eqn.primitive.name == "while":
            cn, bn = p["cond_nconsts"], p["body_nconsts"]
            cond_j, body_j = p["cond_jaxpr"], p["body_jaxpr"]
            cconsts, bconsts = ins[:cn], ins[cn:cn + bn]
            carry = [dict(s) for s in ins[cn + bn:]]
            for _ in range(4):                       # monotone fixpoint
                out = self.run(body_j.jaxpr, list(bconsts) + carry)
                new = [self._join(c, o) for c, o in zip(carry, out)]
                if new == carry:
                    break
                carry = new
            pred = self.run(cond_j.jaxpr, list(cconsts) + carry)
            if (any(self._varying(s) for s in pred)
                    and self._contains_collective(body_j.jaxpr)):
                self._finding(
                    "SP102",
                    "collective inside a while_loop whose continuation "
                    "predicate is device-varying — iteration counts can "
                    "diverge across devices and deadlock the collective",
                    eqn)
            return carry
        # scan: consts + carry + xs; body sees consts + carry + x-slices
        nc, ncar = p["num_consts"], p["num_carry"]
        body = p["jaxpr"]
        consts, carry = ins[:nc], [dict(s) for s in ins[nc:nc + ncar]]
        xs = []
        for s in ins[nc + ncar:]:
            sl = {}
            for ax, v in s.items():
                if v[0] == "shard":
                    # sliced along the scan dim: per-iteration values are
                    # device-distinct (dim identity consumed by the scan)
                    sl[ax] = shard(None) if v[1] in (0, None) else \
                        shard(v[1] - 1)
                else:
                    sl[ax] = v
            xs.append(sl)
        n_y = len(eqn.outvars) - ncar
        ys = [self._rep() for _ in range(n_y)]
        for _ in range(4):
            out = self.run(body.jaxpr, list(consts) + carry + xs)
            new = [self._join(c, o) for c, o in zip(carry, out[:ncar])]
            ys = [self._join(y, o) for y, o in zip(ys, out[ncar:])]
            if new == carry:
                break
            carry = new
        stacked = []
        for y in ys:
            stacked.append({ax: (shard(v[1] + 1) if v[0] == "shard"
                                 and v[1] is not None else v)
                            for ax, v in y.items()})
        return carry + stacked

    def _cond(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        branches = eqn.params["branches"]
        pred, rest = ins[0], ins[1:]
        if self._varying(pred) and any(
                self._contains_collective(b.jaxpr) for b in branches):
            self._finding(
                "SP102",
                "collective inside a lax.cond branch selected by a "
                "device-varying predicate — devices take different "
                "branches and the collective deadlocks", eqn)
        outs = None
        for b in branches:
            res = self.run(b.jaxpr, rest)
            outs = res if outs is None else [self._join(a, o)
                                             for a, o in zip(outs, res)]
        return outs if outs is not None else [self._rep()
                                              for _ in eqn.outvars]

    # -- generic data movement ---------------------------------------------
    def _combine(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        prim = eqn.primitive.name
        if prim == "gather":
            return self._gather(eqn, ins)
        if prim.startswith("scatter"):
            return self._scatter(eqn, ins)
        outs = []
        for o_i in range(len(eqn.outvars)):
            out: AxisStates = {}
            for ax in self.axes:
                vals = [s.get(ax, REP) for s in ins]
                if any(v == OVER for v in vals):
                    out[ax] = OVER
                elif any(v == PART for v in vals):
                    out[ax] = PART
                elif any(v[0] == "shard" for v in vals):
                    pairs, reduced = set(), False
                    for i, v in enumerate(vals):
                        if v[0] != "shard":
                            continue
                        d = self._map_dim(eqn, i, v[1], o_i)
                        if d == "reduced":
                            reduced = True
                        else:
                            pairs.add((d, _shard_tag(v)))
                    if reduced:
                        out[ax] = PART
                    elif len(pairs) == 1:
                        d, tag = pairs.pop()
                        out[ax] = shard(d, tag)
                    else:
                        out[ax] = shard(None)
                else:
                    out[ax] = REP
            outs.append(out)
        return outs

    def _map_dim(self, eqn, i: int, dim: Optional[int], o_i: int):
        """Where input ``i``'s sharded dimension ``dim`` lands in output
        ``o_i``: a new dim index, ``"reduced"`` (summed away → partial), or
        None (unknown — stays device-distinct with unknown dim)."""
        if dim is None:
            return None
        prim, p = eqn.primitive.name, eqn.params
        in_shape = tuple(getattr(eqn.invars[i].aval, "shape", ()))
        out_shape = tuple(getattr(eqn.outvars[o_i].aval, "shape", ()))
        if prim in ("reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
                    "reduce_and", "reduce_or", "argmax", "argmin"):
            axes = tuple(p.get("axes", ()))
            if dim in axes:
                return "reduced"
            return dim - sum(1 for a in axes if a < dim)
        if prim == "broadcast_in_dim":
            bd = p["broadcast_dimensions"]
            return bd[dim] if dim < len(bd) else None
        if prim == "transpose":
            return list(p["permutation"]).index(dim)
        if prim == "squeeze":
            dims = p["dimensions"]
            if dim in dims:
                return None
            return dim - sum(1 for a in dims if a < dim)
        if prim == "reshape":
            b = math.prod(in_shape[:dim]) if in_shape else 1
            acc = 1
            for j, s in enumerate(out_shape):
                if acc == b and dim < len(in_shape) and s == in_shape[dim]:
                    return j
                acc *= s
            return None
        if prim == "concatenate":
            return None if dim == p["dimension"] else dim
        if prim == "dot_general":
            (lc, rc), (lb, rb) = p["dimension_numbers"]
            lhs_rank = len(getattr(eqn.invars[0].aval, "shape", ()))
            rhs_rank = len(getattr(eqn.invars[1].aval, "shape", ()))
            if i == 0:
                if dim in lc:
                    return "reduced"
                if dim in lb:
                    return list(lb).index(dim)
                free = [d for d in range(lhs_rank)
                        if d not in lc and d not in lb]
                return len(lb) + free.index(dim)
            if i == 1:
                if dim in rc:
                    return "reduced"
                if dim in rb:
                    return list(rb).index(dim)
                free_l = lhs_rank - len(lc) - len(lb)
                free = [d for d in range(rhs_rank)
                        if d not in rc and d not in rb]
                return len(lb) + free_l + free.index(dim)
            return None
        if in_shape == out_shape:
            return dim
        if len(in_shape) == len(out_shape):
            return dim
        return None

    def _gather(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        p = eqn.params
        dn = p["dimension_numbers"]
        slice_sizes = tuple(p["slice_sizes"])
        op_shape = tuple(eqn.invars[0].aval.shape)
        idx_shape = tuple(eqn.invars[1].aval.shape)
        out_rank = len(eqn.outvars[0].aval.shape)
        offset = tuple(dn.offset_dims)
        collapsed = tuple(dn.collapsed_slice_dims)
        start_map = tuple(dn.start_index_map)
        batch_out = [k for k in range(out_rank) if k not in offset]
        pass_dims = [d for d in range(len(op_shape)) if d not in collapsed]
        out: AxisStates = {}
        for ax in self.axes:
            op_st = ins[0].get(ax, REP)
            ix_st = ins[1].get(ax, REP)
            if OVER in (op_st, ix_st):
                out[ax] = OVER
                continue
            if PART in (op_st, ix_st):
                out[ax] = PART
                continue
            pairs = set()
            if op_st[0] == "shard":
                d = op_st[1]
                indexed = (d is not None and d in start_map
                           and d < len(slice_sizes)
                           and slice_sizes[d] < op_shape[d])
                if indexed and _shard_tag(op_st) == ROWS:
                    # globally-indexed rows split across devices: each
                    # device resolves global coordinates against its LOCAL
                    # shard — the missing-all_gather bug
                    self._finding(
                        "SP004",
                        f"gather indexes into dimension {d} of a value "
                        f"row-sharded over axis {ax!r} — each device "
                        f"resolves global indices against its local "
                        f"shard; all_gather the operand (or use the "
                        f"rowsharded path) first", eqn)
                    pairs.add((None, None))
                elif indexed:
                    # owner-aligned local gather (sort/permutation within
                    # the device's own nnz shard): device-distinct result
                    pairs.add((None, None))
                elif (d is not None and d in pass_dims
                      and pass_dims.index(d) < len(offset)):
                    pairs.add((offset[pass_dims.index(d)],
                               _shard_tag(op_st)))
                else:
                    pairs.add((None, None))
            if ix_st[0] == "shard":
                d = ix_st[1]
                # the trailing index-vector dim is consumed; others batch
                if (d is not None and d < len(idx_shape) - 1
                        and d < len(batch_out)):
                    pairs.add((batch_out[d], None))
                else:
                    pairs.add((None, None))
            if len(pairs) == 1:
                d, tag = pairs.pop()
                out[ax] = shard(d, tag)
            elif pairs:
                out[ax] = shard(None)
            else:
                out[ax] = REP
        return [out]

    def _scatter(self, eqn, ins: List[AxisStates]) -> List[AxisStates]:
        additive = eqn.primitive.name in ("scatter-add", "scatter-mul")
        dn = eqn.params["dimension_numbers"]
        uw = tuple(dn.update_window_dims)
        iw = tuple(dn.inserted_window_dims)
        op_rank = len(eqn.invars[0].aval.shape)
        window_op_dims = [d for d in range(op_rank) if d not in iw]
        out: AxisStates = {}
        for ax in self.axes:
            op_st = ins[0].get(ax, REP)
            ix_st = ins[1].get(ax, REP)
            up_st = ins[2].get(ax, REP) if len(ins) > 2 else REP
            if any(v == OVER for v in (op_st, ix_st, up_st)):
                out[ax] = OVER
                continue
            if any(v == PART for v in (op_st, ix_st, up_st)):
                out[ax] = PART
                continue
            part, dims = False, set()
            if up_st[0] == "shard":
                d = up_st[1]
                if d is not None and d in uw and uw.index(d) < len(
                        window_op_dims):
                    dims.add(window_op_dims[uw.index(d)])
                else:
                    # device-distinct updates scattered into shared slots:
                    # each device accumulates only its own contributions
                    part = additive
                    if not additive:
                        dims.add(None)
            if ix_st[0] == "shard":
                part = additive
                if not additive:
                    dims.add(None)
            if op_st[0] == "shard":
                dims.add(op_st[1])
            if part:
                out[ax] = PART
            elif dims:
                out[ax] = shard(dims.pop() if len(dims) == 1 else None)
            else:
                out[ax] = REP
        return [out]


# ---------------------------------------------------------------------------
# entry points
# ---------------------------------------------------------------------------

def analyze_jaxpr(closed_jaxpr, in_states: Sequence[AxisStates],
                  axis_sizes: Dict[str, int], label: str = "jaxpr"
                  ) -> Tuple[List[AxisStates], List[Finding], List[str]]:
    """Run the interpreter over a ClosedJaxpr. Returns (output states,
    findings raised during propagation, conservativeness notes)."""
    interp = _Interp(tuple(axis_sizes), label)
    outs = interp.run(closed_jaxpr.jaxpr, list(in_states))
    return outs, interp.findings, interp.notes


def _check_outputs(interp_label: str, out_states: Sequence[AxisStates],
                   allowed_shard_axes: Sequence[str]) -> List[Finding]:
    """Final-state certification: no partial sums or over-reductions may
    escape; shards may escape only over explicitly allowed axes."""
    findings: List[Finding] = []

    def f(rule, msg):
        findings.append(Finding("spmd", 0, 0, rule,
                                f"[{interp_label}] {msg}"))

    for leaf_i, st in enumerate(out_states):
        for ax, v in st.items():
            if v == PART:
                f("SP001", f"partial-sum ESCAPE: output leaf {leaf_i} is "
                           f"an unreduced partial over axis {ax!r} — a "
                           f"psum({ax!r}) is missing")
            elif v == OVER:
                f("SP002", f"output leaf {leaf_i} is over-reduced over "
                           f"axis {ax!r} (a redundant psum upstream)")
            elif v[0] == "shard" and ax not in allowed_shard_axes:
                f("SP003", f"output leaf {leaf_i} is device-distinct over "
                           f"axis {ax!r} but this output must be "
                           f"replicated")
    return findings


def analyze_fn(fn, args: Sequence, in_states: Sequence[AxisStates],
               axis_env: Sequence[Tuple[str, int]],
               expected: Optional[Dict[str, object]] = None,
               label: str = "fn") -> List[Finding]:
    """Fixture/unit entry: trace ``fn(*args)`` under ``axis_env`` and
    certify its outputs. ``in_states`` align with the positional args;
    ``expected`` maps each axis to ``"rep"`` (shards escaping are SP003) or
    ``"shard"`` (device-distinct outputs are legal)."""
    import jax
    env = [tuple(a) for a in axis_env] or None
    try:
        closed = jax.make_jaxpr(fn, axis_env=env)(*args)
    except Exception as e:
        return [Finding("spmd", 0, 0, "SP000",
                        f"[{label}] failed to trace: "
                        f"{type(e).__name__}: {e}")]
    sizes = dict(axis_env)
    outs, findings, _ = analyze_jaxpr(closed, in_states, sizes, label)
    expected = expected or {}
    allowed = [ax for ax in sizes
               if str(expected.get(ax, "shard")).startswith("shard")]
    return findings + _check_outputs(label, outs, allowed)


# ---------------------------------------------------------------------------
# the planner-IR sweep
# ---------------------------------------------------------------------------

def _dedupe(denses: Sequence) -> Tuple[List, List[int]]:
    uniq: List = []
    posmap: List[int] = []
    for d in denses:
        for k, u in enumerate(uniq):
            if d is u:
                posmap.append(k)
                break
        else:
            posmap.append(len(uniq))
            uniq.append(d)
    return uniq, posmap


def _operand_states(axes: Sequence[str], data_axes: Sequence[str],
                    model_axes: Sequence[str], rowsharded: bool,
                    n_dense: int) -> Tuple[List[AxisStates],
                                           List[AxisStates]]:
    """(sparse-leaf states [values, indices, valid], per-dense states).

    Data axes shard the nonzeros (every sparse leaf is row-sharded along
    its leading nnz dim); factor rows are additionally sharded when
    ``rowsharded``. Model axes shard factor COLUMNS (dim 1) while the
    sparse leaves are replicated (the local arrays hold local rank)."""
    sp = {ax: REP for ax in axes}
    dn = {ax: REP for ax in axes}
    for ax in data_axes:
        sp[ax] = shard(0)
        # rowsharded factors are GLOBALLY-indexed row spaces split across
        # devices (the ROWS tag arms the SP004 gather check); the sparse
        # leaves are owner-aligned nnz shards, untagged
        dn[ax] = shard(0, ROWS) if rowsharded else REP
    for ax in model_axes:
        dn[ax] = shard(1)
    sparse_states = [dict(sp) for _ in range(3)]
    return sparse_states, [dict(dn) for _ in range(n_dense)]


def _allowed_shard_axes(family: str, path: str,
                        data_axes: Sequence[str],
                        model_axes: Sequence[str]) -> List[str]:
    """Mesh axes over which a device-distinct OUTPUT is legal for this
    family: TTTP outputs ride the data-sharded nonzeros; the rowsharded
    MTTKRP's reduce-scatter leaves row-ownership on the data axes; MTTKRP/
    CG outputs stay column-sharded under model parallelism (the caller
    all-gathers or keeps rank-local factors)."""
    allowed: List[str] = []
    if family == "tttp" or path == "rowsharded":
        allowed += list(data_axes)
    if family in ("mttkrp", "mttkrp_partial", "cg_matvec", "ttm"):
        allowed += list(model_axes)
    return allowed


def _trace_execution(ir, path: str, st, denses: Sequence, ctx, config,
                     axis_env: Sequence[Tuple[str, int]]):
    """make_jaxpr of one (IR, path) execution with the sparse tensor's
    values/indices/valid AND the dense operands as jaxpr inputs — so every
    operand carries its replication state into the interpreter (unlike the
    contracts sweep, which closes over concrete indices)."""
    import jax

    from repro.core.sparse_tensor import SparseTensor
    from repro.planner import dispatch as pdispatch

    run_ctx = _FaultCtx(ctx, _FAULT) if _FAULT is not None else ctx
    uniq, posmap = _dedupe(denses)

    def aval(a):
        return jax.ShapeDtypeStruct(tuple(a.shape), a.dtype)

    if st is None:
        def f(*args):
            return pdispatch.execute(ir, path, list(args), ctx=run_ctx,
                                     config=config)
        args = tuple(aval(d) for d in uniq)
    else:
        def f(values, indices, valid, *uds):
            st2 = SparseTensor(indices, values, valid, st.shape, st.nnz,
                               st.sorted_mode, st.nnz_rows)
            ops: List = [None] * len(ir.operands)
            ops[ir.sparse_pos] = st2
            for pos, k in zip(ir.dense_positions, posmap):
                ops[pos] = uds[k]
            return pdispatch.execute(ir, path, ops, ctx=run_ctx,
                                     config=config)
        args = (aval(st.values), aval(st.indices),
                aval(st.valid)) + tuple(aval(d) for d in uniq)

    env = [tuple(a) for a in axis_env] or None
    try:
        closed = jax.make_jaxpr(f, axis_env=env)(*args)
    except Exception:
        if env is None:
            raise
        # ambient axis frames (inside shard_map) already bind the names
        closed = jax.make_jaxpr(f)(*args)
    return closed, posmap


def _analyze_execution(ir, path: str, st, denses: Sequence, ctx, config,
                       axis_env: Sequence[Tuple[str, int]], family: str,
                       rowsharded: bool, label: str) -> List[Finding]:
    try:
        closed, posmap = _trace_execution(ir, path, st, denses, ctx,
                                          config, axis_env)
    except Exception as e:
        return [Finding("spmd", 0, 0, "SP000",
                        f"[{label}] failed to trace: "
                        f"{type(e).__name__}: {e}")]
    sizes = dict(axis_env)
    axes = tuple(sizes)
    data_axes = tuple(ax for ax in _axis_names(ctx.data) if ax in axes)
    model_axes = tuple(ax for ax in _axis_names(ctx.model) if ax in axes)
    sp_states, base_dense = _operand_states(axes, data_axes, model_axes,
                                            rowsharded, len(denses))
    uniq_states = {}
    for k, s in zip(posmap, base_dense):
        uniq_states.setdefault(k, s)
    dense_states = [uniq_states[k] for k in sorted(uniq_states)]
    in_states = (dense_states if st is None
                 else sp_states + dense_states)
    outs, findings, _ = analyze_jaxpr(closed, in_states, sizes, label)
    allowed = _allowed_shard_axes(family, path, data_axes, model_axes)
    return findings + _check_outputs(label, outs, allowed)


def check_cases(cases=None, orders: Sequence[int] = (3, 4, 5)
                ) -> List[Finding]:
    """The exhaustive sweep: every candidate path of every
    ``contracts.iter_cases`` grid point, certified for collective
    soundness. Pallas dispatch is forced OFF during tracing so the jaxprs
    contain the jnp reference paths the interpreter models (the Pallas
    kernels compute identically and are certified separately by the VMEM
    pass)."""
    from repro.analysis import contracts
    from repro.planner import cost as pcost

    if cases is None:
        cases = contracts.iter_cases(orders)
    findings: List[Finding] = []
    old = os.environ.get("REPRO_USE_PALLAS")
    os.environ["REPRO_USE_PALLAS"] = "0"
    try:
        for case in cases:
            rowsh = (case.ir.dist.rowsharded
                     if case.ir.dist is not None else False)
            for path in pcost.candidate_paths(case.ir):
                findings += _analyze_execution(
                    case.ir, path, case.st, case.denses, case.ctx,
                    case.config, case.axis_env, case.family, rowsh,
                    label=f"{case.name}/{path}")
    finally:
        if old is None:
            os.environ.pop("REPRO_USE_PALLAS", None)
        else:
            os.environ["REPRO_USE_PALLAS"] = old
    return findings


def run(orders: Sequence[int] = (3, 4, 5)) -> List[Finding]:
    return check_cases(orders=orders)


# ---------------------------------------------------------------------------
# online certification (plan_contraction(..., validate_spmd=True))
# ---------------------------------------------------------------------------

def _family_tag(ir) -> str:
    from repro.planner import ir as pir
    if ir.kind == pir.TTTP:
        return "tttp"
    if ir.kind == pir.REDUCE:
        return "reduce"
    if ir.kind == pir.TTM:
        return "ttm"
    if ir.kind == pir.MTTKRP:
        return "mttkrp" if pir.is_classic_mttkrp(ir) else "mttkrp_partial"
    if ir.kind == pir.CG_MATVEC:
        return "cg_matvec"
    return "dense"


def certify_plan(ir, paths: Sequence[str], operands: Sequence, ctx,
                 config) -> None:
    """Raise :class:`SpmdContractError` unless every candidate path of this
    concrete call is collective-sound: no partial-sum escapes, no redundant
    or wrong-axis psums, no gathers into sharded dimensions. Called by
    ``plan_contraction(..., validate_spmd=True)``; safe under tracing
    (only operand avals are consulted)."""
    dist = ir.dist
    if dist is None:
        axis_env: List[Tuple[str, int]] = []
    else:
        axis_env = []
        data_names = _axis_names(ctx.data)
        model_names = _axis_names(ctx.model)
        if data_names:
            per = max(1, round(dist.data_size ** (1 / len(data_names)))) \
                if len(data_names) > 1 else dist.data_size
            axis_env += [(n, per) for n in data_names]
        if model_names:
            axis_env += [(n, dist.model_size) for n in model_names]
    if not axis_env:
        return  # local: no mesh axes, nothing to certify
    st = operands[ir.sparse_pos] if ir.sparse_pos is not None else None
    denses = [operands[i] for i in ir.dense_positions]
    family = _family_tag(ir)
    findings: List[Finding] = []
    for path in paths:
        findings += _analyze_execution(
            ir, path, st, denses, ctx, config, axis_env, family,
            dist.rowsharded, label=f"{ir.expr}/{path}")
    if findings:
        detail = "\n".join(f.format() for f in findings)
        raise SpmdContractError(
            f"SPMD certification failed for {ir.expr!r} — the plan's "
            f"collective schedule is unsound:\n{detail}")
