"""Low-overhead, jit-aware tracing spans (DESIGN.md §11).

``span(name, **attrs)`` yields a live Span when (a) tracing is enabled and
(b) the call is NOT under a jax trace; otherwise it yields a shared no-op
span. The no-op path is safe inside ``jax.jit``-traced code: it touches no
tracers, performs no host sync, and `fence` returns its argument untouched
— so instrumented library code compiles identically with tracing on or
off. Live spans nest through a thread-local stack: each finished span
folds its record into its parent, and a finished ROOT span's full tree is
retained (``last_root``) for the experiment harness to attach to its
per-sweep metric history.

Timing discipline: a live span's duration is wall time between ``__enter__``
and ``__exit__``; for device work the caller must fence the result
(``sp.fence(out)``) so async dispatch doesn't end the span early. Every
finished span feeds the registry's timing histogram under its slash-joined
path and, when a JSONL sink is installed, emits one flat event line. A live
span is also a ``jax.profiler.TraceAnnotation`` named by its path, so under
``jax.profiler`` it lands on the trace's host plane, on the device's clock.

``scope(name)`` names traced device work instead: a ``jax.named_scope``
that puts ``name`` into the ``op_name`` metadata of every operation traced
under it, so a compiled program's operations (and the profiler's device
events for them) say which kernel family and solver phase they belong to.
It changes metadata only, so it is always on and costs nothing at run time.
"""
from __future__ import annotations

import contextlib
import os
import threading
import time
from typing import Any, Dict, Iterator, Optional

from repro.obs.metrics import JsonlSink, MetricsRegistry, _jsonable

_REGISTRY = MetricsRegistry()
_SINK: Optional[JsonlSink] = None
_ENABLED = os.environ.get("REPRO_TRACE", "0") == "1"
_TLS = threading.local()


def get_registry() -> MetricsRegistry:
    return _REGISTRY


def enabled() -> bool:
    return _ENABLED


def enable(jsonl: Optional[str] = None) -> None:
    """Turn tracing on process-wide; ``jsonl`` installs an event sink."""
    global _ENABLED, _SINK
    if jsonl is not None:
        if _SINK is not None:
            _SINK.close()
        _SINK = JsonlSink(jsonl)
    _ENABLED = True


def disable() -> None:
    """Turn tracing off and close any installed sink."""
    global _ENABLED, _SINK
    _ENABLED = False
    if _SINK is not None:
        _SINK.close()
        _SINK = None


def sink() -> Optional[JsonlSink]:
    return _SINK


def emit_event(record: Dict[str, Any]) -> None:
    """Write one non-span event (counter snapshot, ingest stats, …) to the
    sink, if one is installed."""
    if _SINK is not None:
        _SINK.emit(record)


def trace_clean() -> bool:
    """True when NOT under a jax trace (jit/grad/vmap/shard_map tracing).
    Deferred jax import: obs must stay importable before jax initializes
    (the launch drivers set XLA flags first)."""
    import jax
    return jax.core.trace_ctx.is_top_level()


_trace_clean = trace_clean


def _stack() -> list:
    st = getattr(_TLS, "stack", None)
    if st is None:
        st = _TLS.stack = []
    return st


def last_root() -> Optional[Dict[str, Any]]:
    """The most recently FINISHED root span's nested record (this thread)."""
    return getattr(_TLS, "last_root", None)


class Span:
    """A live span. ``record`` holds the finished nested dict after exit."""

    __slots__ = ("name", "path", "attrs", "children", "record")
    live = True

    def __init__(self, name: str, path: str, attrs: Dict[str, Any]):
        self.name = name
        self.path = path
        self.attrs = {k: _jsonable(v) for k, v in attrs.items()}
        self.children: list = []
        self.record: Optional[Dict[str, Any]] = None

    def annotate(self, **kv) -> None:
        self.attrs.update({k: _jsonable(v) for k, v in kv.items()})

    def fence(self, x):
        """block_until_ready the pytree ``x`` so the span's duration covers
        the device work that produced it; returns ``x``."""
        import jax
        return jax.block_until_ready(x)


class _NoopSpan:
    """Shared no-op span: used when disabled or under a jax trace."""

    __slots__ = ()
    live = False
    record = None
    children: list = []

    def annotate(self, **kv) -> None:
        pass

    def fence(self, x):
        return x


_NOOP = _NoopSpan()


@contextlib.contextmanager
def span(name: str, **attrs) -> Iterator[Any]:
    """Context manager for one traced region (see module docstring)."""
    if not _ENABLED or not _trace_clean():
        yield _NOOP
        return
    import jax               # loaded already: _trace_clean imported it
    st = _stack()
    path = (st[-1].path + "/" + name) if st else name
    sp = Span(name, path, attrs)
    st.append(sp)
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(path):
            yield sp
    finally:
        dur = time.perf_counter() - t0
        st.pop()
        rec: Dict[str, Any] = {"kind": "span", "name": sp.name,
                               "path": sp.path, "dur_s": dur}
        if sp.attrs:
            rec["attrs"] = sp.attrs
        if sp.children:
            rec["children"] = sp.children
        sp.record = rec
        _REGISTRY.observe(sp.path, dur)
        if st:
            st[-1].children.append(rec)
        else:
            _TLS.last_root = rec
        if _SINK is not None:
            flat = dict(rec)
            flat.pop("children", None)
            flat["depth"] = len(st) + 1          # 1-based: roots at depth 1
            _SINK.emit(flat)


def scope(name: str):
    """Name the device work traced inside the block (module docstring):
    ``with obs.scope("mttkrp"): ...`` makes every operation traced there
    carry ``.../mttkrp/...`` in its HLO ``op_name``. Scopes nest, as the
    ``jax.named_scope`` this is."""
    import jax
    return jax.named_scope(name)


def counter_add(name: str, value: float = 1.0) -> None:
    """Registry counter bump; no-op while tracing is disabled."""
    if _ENABLED:
        _REGISTRY.counter_add(name, value)


def gauge_set(name: str, value: float) -> None:
    """Registry gauge set; no-op while tracing is disabled."""
    if _ENABLED:
        _REGISTRY.gauge_set(name, value)
