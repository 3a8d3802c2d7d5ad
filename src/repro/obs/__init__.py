"""Observability layer: spans, metrics, and kernel roofline profiling
(DESIGN.md §11).

Disabled by default — every instrumentation point in the library routes
through :func:`span` / :func:`counter_add` / :func:`gauge_set`, which are
no-ops until :func:`enable` is called (or ``REPRO_TRACE=1`` is set) and
are always no-ops under a jax trace, so instrumented code jit-compiles
unchanged. A live span also shows in a ``jax.profiler`` trace. Device work
is named with :func:`scope`, which puts the name into the compiled
operations' ``op_name`` and is always on.

    from repro import obs
    obs.enable(jsonl="trace.jsonl")
    ...                                  # planner/kernel/ingest spans record
    print(obs.get_registry().summary())  # counters, timings, plan table
"""
from repro.obs.metrics import (JsonlSink, MetricsRegistry, PlanRecord,
                               Timing, read_jsonl)
from repro.obs.profile import Machine, hlo_terms, profile_jitted
from repro.obs import trace
from repro.obs.trace import (counter_add, disable, emit_event, enable,
                             enabled, gauge_set, get_registry, last_root,
                             scope, sink, span, trace_clean)

__all__ = [
    "span", "scope", "enable", "disable", "enabled", "get_registry",
    "last_root", "sink", "emit_event", "counter_add", "gauge_set",
    "trace_clean",
    "MetricsRegistry", "Timing", "PlanRecord", "JsonlSink", "read_jsonl",
    "Machine", "hlo_terms", "profile_jitted",
]
