"""Telemetry subsystem tests (DESIGN.md §11): span nesting + aggregation,
JSONL round-trip, jit-safety of the disabled path, planner plan records,
ingest gauges, spans on the profiler's trace, the cost of a live span, and
the named scopes on a compiled ALS sweep."""
import glob
import json
import math
import os
import re
import statistics
import time

import jax
import jax.numpy as jnp
import pytest

from repro import obs
from repro.obs.metrics import MetricsRegistry, Timing, _jsonable


@pytest.fixture(autouse=True)
def _clean_obs():
    """Every test starts and ends with tracing off and a fresh registry."""
    obs.disable()
    obs.get_registry().reset()
    yield
    obs.disable()
    obs.get_registry().reset()


# ---------------------------------------------------------------------------
# registry primitives
# ---------------------------------------------------------------------------

def test_timing_summary_quantiles():
    t = Timing()
    for v in [0.001 * i for i in range(1, 101)]:
        t.observe(v)
    s = t.summary()
    assert s["count"] == 100
    assert s["min_s"] == pytest.approx(0.001)
    assert s["max_s"] == pytest.approx(0.100)
    assert s["mean_s"] == pytest.approx(0.0505)
    assert 0.045 <= s["p50_s"] <= 0.055
    assert 0.090 <= s["p95_s"] <= 0.100


def test_timing_reservoir_bounded():
    t = Timing()
    for i in range(5000):
        t.observe(float(i))
    assert len(t.samples) <= 512
    assert t.count == 5000       # exact stats unaffected by the reservoir
    assert t.max == 4999.0


def test_registry_counters_gauges():
    r = MetricsRegistry()
    r.counter_add("c")
    r.counter_add("c", 2.0)
    r.gauge_set("g", 7.5)
    s = r.summary()
    assert s["counters"]["c"] == 3.0
    assert s["gauges"]["g"] == 7.5
    r.reset()
    assert r.summary() == {"counters": {}, "gauges": {}, "timings": {},
                           "plans": {}}


def test_plan_record_freezes_prediction_and_accumulates():
    r = MetricsRegistry()
    r.record_plan("k", "mttkrp", "kr_first", "ijk,jr,kr->ir",
                  {"flops": 10.0, "seconds": 2.0}, 1.0)
    r.record_plan("k", "mttkrp", "kr_first", "ijk,jr,kr->ir",
                  {"flops": 99.0, "seconds": 99.0}, 3.0)   # ignored: frozen
    p = r.summary()["plans"]["k"]
    assert p["predicted"]["seconds"] == 2.0
    assert p["measured"]["count"] == 2
    assert p["measured_over_predicted"] == pytest.approx(1.0)  # mean 2.0 / 2.0


def test_jsonable_coerces_array_scalars():
    assert _jsonable(jnp.float32(1.5)) == 1.5
    assert _jsonable({"a": (jnp.int32(2), None)}) == {"a": [2, None]}


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------

def test_span_disabled_is_noop():
    with obs.span("x") as sp:
        assert sp.record is None
        assert sp.fence(42) == 42          # fence passes through, no jax call
    assert obs.get_registry().summary()["timings"] == {}


def test_span_nesting_and_aggregation():
    obs.enable()
    with obs.span("outer", tag="t") as outer:
        with obs.span("inner") as inner:
            time.sleep(0.001)
        assert inner.record["path"] == "outer/inner"
    rec = outer.record
    assert rec["name"] == "outer" and rec["path"] == "outer"
    assert rec["attrs"] == {"tag": "t"}
    assert [c["path"] for c in rec["children"]] == ["outer/inner"]
    assert rec["dur_s"] >= rec["children"][0]["dur_s"] >= 0.001
    assert obs.last_root() is rec
    timings = obs.get_registry().summary()["timings"]
    assert timings["outer"]["count"] == 1
    assert timings["outer/inner"]["count"] == 1


def test_span_exception_still_closes():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("boom"):
            raise ValueError("x")
    assert obs.get_registry().summary()["timings"]["boom"]["count"] == 1


def test_jsonl_round_trip(tmp_path):
    path = os.path.join(tmp_path, "t.jsonl")
    obs.enable(jsonl=path)
    with obs.span("a", k=1):
        with obs.span("b"):
            pass
    obs.emit_event({"kind": "custom", "v": jnp.float32(2.0)})
    obs.disable()
    events = obs.read_jsonl(path)
    kinds = [e["kind"] for e in events]
    assert kinds == ["span", "span", "custom"]     # children close first
    by_path = {e.get("path"): e for e in events if e["kind"] == "span"}
    assert by_path["a"]["attrs"] == {"k": 1}
    assert by_path["a/b"]["depth"] == 2
    assert "children" not in by_path["a"]          # sink stream stays flat
    assert events[2]["v"] == 2.0
    for e in events:
        json.dumps(e)                              # every event JSON-clean


# ---------------------------------------------------------------------------
# jit-safety: the enabled path must be a no-op inside traced code
# ---------------------------------------------------------------------------

def test_span_inside_jit_no_tracer_leak():
    obs.enable()

    def f(x):
        with obs.span("traced", n=3) as sp:
            return sp.fence(x * 2.0)

    eager = f(jnp.arange(4.0))
    jitted = jax.jit(f)(jnp.arange(4.0))
    assert jnp.allclose(eager, jitted)
    timings = obs.get_registry().summary()["timings"]
    # the eager call recorded; the traced call must NOT have
    assert timings["traced"]["count"] == 1


def test_disabled_span_compiles_identically():
    def f(x):
        with obs.span("s") as sp:
            return sp.fence(jnp.sum(x * x))

    x = jnp.arange(8.0)
    assert jax.jit(f)(x) == f(x)


# ---------------------------------------------------------------------------
# integration: planner plan table, kernel spans, ingest gauges
# ---------------------------------------------------------------------------

def test_planner_records_predicted_vs_measured():
    from repro import planner
    from repro.core.sparse_tensor import SparseTensor

    st = SparseTensor.random(jax.random.PRNGKey(0), (30, 20, 10), 300)
    fs = [jax.random.normal(jax.random.PRNGKey(i), (d, 4))
          for i, d in enumerate(st.shape)]
    obs.enable()
    out = planner.planned_mttkrp(st, [None, fs[1], fs[2]], mode=0)
    out2 = planner.planned_mttkrp(st, [None, fs[1], fs[2]], mode=0)
    assert jnp.allclose(out, out2)
    plans = obs.get_registry().summary()["plans"]
    assert len(plans) == 1
    (key, p), = plans.items()
    assert "m300" in key and p["kind"] == "mttkrp"
    assert p["measured"]["count"] == 2
    assert p["predicted"]["seconds"] > 0
    assert set(p["predicted"]) >= {"flops", "mem", "comm", "seconds"}
    # the dispatch span landed in the timing histogram under planner/<kind>
    timings = obs.get_registry().summary()["timings"]
    assert any(k.startswith("planner/mttkrp/") for k in timings), \
        timings.keys()


def test_kernel_wrapper_spans():
    from repro.core.sparse_tensor import SparseTensor
    from repro.kernels import ops as kops

    st = SparseTensor.random(jax.random.PRNGKey(2), (20, 15, 10), 150)
    fs = [jax.random.normal(jax.random.PRNGKey(30 + i), (d, 4))
          for i, d in enumerate(st.shape)]
    obs.enable()
    kops.tttp_values(st, fs, use_pallas=False)
    out = kops.mttkrp_bucketed(st.row_buckets(0, 8), [None, fs[1], fs[2]],
                               num_rows=20, use_pallas=False)
    assert out.shape == (20, 4)
    timings = obs.get_registry().summary()["timings"]
    assert "kernel/tttp" in timings
    assert "kernel/mttkrp_bucketed" in timings


def test_planner_result_unchanged_by_tracing():
    from repro import planner
    from repro.core.sparse_tensor import SparseTensor

    st = SparseTensor.random(jax.random.PRNGKey(1), (25, 15, 10), 200)
    fs = [jax.random.normal(jax.random.PRNGKey(10 + i), (d, 3))
          for i, d in enumerate(st.shape)]
    off = planner.planned_mttkrp(st, [None, fs[1], fs[2]], mode=0)
    obs.enable()
    on = planner.planned_mttkrp(st, [None, fs[1], fs[2]], mode=0)
    assert jnp.allclose(off, on)


def test_ingest_telemetry(tmp_path):
    from repro.data import streaming

    obs.enable()
    chunks = streaming.make_stream("function", 0, (40, 30, 20), 2000, 512)
    ing = streaming.StreamingIngest((40, 30, 20), num_shards=2)
    for c in chunks:
        ing.add(c)
    ing.finalize()
    stats = ing.stats
    assert stats.ingest_seconds > 0
    assert stats.mnnz_per_s > 0
    assert stats.peak_rss_mb > 0
    s = obs.get_registry().summary()
    assert s["gauges"]["ingest/mnnz_per_s"] == pytest.approx(
        stats.mnnz_per_s)
    assert s["counters"]["ingest/entries_read"] >= 2000


# ---------------------------------------------------------------------------
# overhead bound: a live span costs under 2% of the steps it wraps
# ---------------------------------------------------------------------------

# The program's shortest spanned step is an eager kernel call of a few
# milliseconds; 2% of a 5 ms step is 100 us. A live span measures about
# 10 us on a CPU container; the budget leaves room for a loaded machine.
SPAN_BUDGET_S = 100e-6


def test_tracing_overhead_under_two_percent():
    """The live path's cost per span (enter, fence on a ready array, exit,
    registry and profiler annotation), the median of several repeats of a
    few thousand spans: a bound that load from other processes does not
    swing the way a comparison of two timed runs does."""
    x = jax.block_until_ready(jnp.ones(8))

    def per_span(n=2000):
        t0 = time.perf_counter()
        for _ in range(n):
            with obs.span("step") as sp:
                sp.fence(x)
        return (time.perf_counter() - t0) / n

    obs.enable()
    per_span(200)                                  # warm the path
    cost = statistics.median(per_span() for _ in range(7))
    assert cost < SPAN_BUDGET_S, cost
    assert obs.get_registry().summary()["timings"]["step"]["count"] == \
        200 + 7 * 2000


def test_live_span_lands_on_the_profiler_trace(tmp_path):
    """A live span is also a profiler annotation: it shows on a ``/host:``
    plane of a ``jax.profiler`` trace under its path."""
    from jax.profiler import ProfileData

    obs.enable()
    jax.profiler.start_trace(str(tmp_path))
    with obs.span("outer"):
        with obs.span("inner") as sp:
            sp.fence(jnp.arange(4.0) * 2.0)
    jax.profiler.stop_trace()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert {"outer", "outer/inner"} <= names


def test_kernel_dispatch_bumps_no_counter():
    """Kernel routing leaves no trace-time counter behind: the route is an
    attribute of the eager kernel span."""
    from repro.core.sparse_tensor import SparseTensor
    from repro.kernels import ops as kops

    st = SparseTensor.random(jax.random.PRNGKey(2), (20, 15, 10), 150)
    fs = [jax.random.normal(jax.random.PRNGKey(30 + i), (d, 4))
          for i, d in enumerate(st.shape)]
    obs.enable()
    kops.tttp_values(st, fs, use_pallas=False)
    jax.jit(lambda s, f: kops.tttp_values(s, f))(st, fs)
    assert obs.get_registry().summary()["counters"] == {}


# ---------------------------------------------------------------------------
# named scopes: every operation of a compiled ALS sweep names its mode, and
# every gather and scatter its kernel family
# ---------------------------------------------------------------------------

_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = (.*)$")
# what XLA adds itself, or what runs nothing on its own
_PLUMBING = ("parameter", "constant", "get-tuple-element", "tuple", "copy",
             "bitcast")


def _instructions(hlo_text):
    """``(computation, name, opcode, op_name, elements, fill)`` of every
    instruction (``fill``: every operand a constant, so it reads nothing),
    and the computations called as fusions or reducers."""
    out, called, comp = [], set(), None
    for line in hlo_text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        m = _INSTRUCTION.match(line)
        if comp is None or not m:
            continue
        name, rest = m.groups()
        called.update(re.findall(r"(?:calls|to_apply)=%?([\w.\-]+)", rest))
        opcode = re.search(r"(?:^|\s)([a-z][\w\-]*)\(([^)]*)\)", rest)
        dims = re.match(r"\w+\[([\d,]*)\]", rest)
        elements = 0 if dims is None else math.prod(
            int(d) for d in dims.group(1).split(",") if d)
        op_name = re.search(r'op_name="([^"]*)"', rest)
        operands = opcode.group(2).split(", ") if opcode else []
        fill = bool(operands) and all(
            o.lstrip("%").startswith("constant") for o in operands)
        out.append((comp, name, opcode.group(1) if opcode else "",
                    op_name.group(1) if op_name else None, elements, fill))
    return out, called


def _compiled_sweep_text(nnz=2000, **kw):
    from repro.core.completion import als_sweep
    from repro.data import synthetic

    key = jax.random.PRNGKey(0)
    shape = (40, 30, 20)
    st = synthetic.function_tensor(key, shape, nnz)
    omega = st.with_values(jnp.ones_like(st.values))
    fs = tuple(jax.random.normal(jax.random.fold_in(key, d), (n, 5))
               for d, n in enumerate(shape))
    fn = jax.jit(lambda s, o, f: tuple(als_sweep(s, o, list(f), 1e-4,
                                                 cg_iters=20, **kw)))
    return fn.lower(st, omega, fs).compile().as_text(), st.cap


def _check_scopes(text, cap, holders):
    """Every operation of the compiled sweep ``text`` names its mode, and
    every gather and scatter one of ``holders``."""
    insts, called = _instructions(text)
    top = [i for i in insts if i[0] not in called
           and i[2] not in ("while", "conditional", "call")]
    scoped = [i for i in top if i[3] is not None and i[2] != "parameter"]
    assert scoped
    for comp, name, opcode, op_name, _, _ in scoped:
        assert re.search(r"/mode_\d/", op_name), (name, op_name)
    # what carries no op_name is XLA's own plumbing, or a fusion of it,
    # and none of it runs over the nonzeros; a fill of constants reads
    # nothing
    for comp, name, opcode, op_name, elements, fill in top:
        if op_name is None:
            assert opcode in _PLUMBING + ("fusion",), (name, opcode)
            if opcode in ("copy", "fusion") and not fill:
                assert elements < cap, (name, opcode, elements)
    # every gather and scatter, fused or not, names where it runs
    moves = [i for i in insts if i[2] in ("gather", "scatter")]
    assert {i[2] for i in moves} == {"gather", "scatter"}
    for comp, name, opcode, op_name, _, _ in moves:
        assert re.search(f"/({'|'.join(holders)})/", op_name or ""), (
            name, op_name)
    # the solver's phases are named too
    names = " ".join(i[3] for i in scoped)
    for phase in ("rhs", "matvec", "cg_update"):
        assert f"/{phase}/" in names, phase


def test_compiled_sweep_operations_carry_their_scopes():
    """The default sweep of a tensor of 50, 67 and 100 nonzeros a row:
    every mode on the row-slab operator (64 slots a slab, then 128). A
    gather or scatter of a CG matvec names its kernel family; the build's
    gathers name ``gram_build``, the right-hand side's sorted scatter
    ``rhs``. The zeros of a build step past the last row are a fill."""
    text, cap = _compiled_sweep_text()
    assert "/mode_0/gram_build/" in text
    _check_scopes(text, cap, ("tttp", "mttkrp", "gram_build", "rhs"))


def test_compiled_coo_sweep_operations_carry_their_scopes():
    """The same sweep on the COO Gram matvec (kept by an explicit
    planner path): every gather and scatter names its kernel family."""
    text, cap = _compiled_sweep_text(mttkrp_path="all_at_once")
    assert "gram_build" not in text
    _check_scopes(text, cap, ("tttp", "mttkrp"))


@pytest.mark.parametrize("family", ["mttkrp", "cg_matvec"])
def test_trace_time_fallback_bumps_counter(family):
    """Under jit the cached bucket pattern does not cross the tracer
    boundary: the bucketed/fused path falls back, and says so once per
    trace in a ``dispatch/fallback/*`` counter."""
    from repro import planner
    from repro.core.sparse_tensor import SparseTensor

    st = SparseTensor.random(jax.random.PRNGKey(3), (20, 15, 10), 150)
    fs = [jax.random.normal(jax.random.PRNGKey(40 + i), (d, 4))
          for i, d in enumerate(st.shape)]
    if family == "mttkrp":
        fn = jax.jit(lambda s, f: planner.planned_mttkrp(
            s, [None, f[1], f[2]], 0, path="bucketed"))
        name = "dispatch/fallback/mttkrp_bucketed"
    else:
        fn = jax.jit(lambda s, f: planner.planned_cg_matvec(
            s, list(f), 0, f[0], path="fused"))
        name = "dispatch/fallback/cg_matvec_fused"
    obs.enable()
    fn(st, fs)
    fn(st, fs)                                  # cached: no second trace
    assert obs.get_registry().summary()["counters"][name] == 1
