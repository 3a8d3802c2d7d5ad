"""Telemetry subsystem tests (DESIGN.md §11): span nesting + aggregation,
JSONL round-trip, jit-safety of the disabled path, planner plan records,
ingest gauges, and the measured-overhead bound on a real ALS run."""
import json
import os
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
# overhead bound: tracing a real 10-sweep ALS run costs <2%
# ---------------------------------------------------------------------------

def test_tracing_overhead_under_two_percent():
    from repro.core.completion import als_sweep
    from repro.core.sparse_tensor import SparseTensor

    st = SparseTensor.random(jax.random.PRNGKey(3), (60, 50, 40), 4000)
    omega = st.with_values(jnp.ones_like(st.values))
    fs0 = [jax.random.normal(jax.random.PRNGKey(20 + i), (d, 6)) / 6 ** 0.5
           for i, d in enumerate(st.shape)]
    step = jax.jit(lambda fs: tuple(als_sweep(st, omega, list(fs), 1e-3,
                                              cg_iters=4)))

    def run_sweeps():
        fs = tuple(fs0)
        for i in range(10):
            with obs.span("sweep", i=i) as sp:
                fs = step(fs)
                sp.fence(fs)
        jax.block_until_ready(fs)
        return fs

    run_sweeps()                                   # compile once
    def best_of(n):
        best = float("inf")
        for _ in range(n):
            t0 = time.perf_counter()
            run_sweeps()
            best = min(best, time.perf_counter() - t0)
        return best

    obs.disable()
    base = best_of(5)
    obs.enable()
    traced = best_of(5)
    obs.disable()
    # 2% of a ~100ms 10-sweep run is ~2ms of timer noise territory on a
    # shared container — allow a small absolute epsilon alongside the bound
    if traced > base * 1.02 + 2e-3:
        # noise is one-sided (other tenants only slow you down): re-measure
        # both arms once before declaring a real tracing regression
        base = min(base, best_of(5))
        obs.enable()
        traced = min(traced, best_of(5))
        obs.disable()
    assert traced <= base * 1.02 + 2e-3, (traced, base)
    reg = obs.get_registry().summary()
    assert reg["timings"]["sweep"]["count"] in (50, 100)  # 10 sweeps x reps


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
