"""The trace reduction, on a trace recorded on a TPU v5e (a few
milliseconds of ALS sweeps on a 3,000 x 500 x 60 tensor, rank 32:
``control.py fixture``) and on intervals made by hand."""
import json

import pytest

import tracecut
from conftest import HERE

FIXTURE = HERE / "data" / "tiny_sweep.xplane.pb"


def test_union_merges_and_clips():
    merged = tracecut._union([(5, 7), (0, 2), (1, 3), (6, 9)], 1, 8)
    assert merged == [[1, 3], [5, 8]]
    assert tracecut._length(merged) == 5


def test_recorded_trace_reduces_to_its_committed_numbers():
    got = tracecut.reduce(str(FIXTURE))
    want = json.loads((HERE / "data" / "tiny_sweep.reduced.json")
                      .read_text())
    assert got == want


def test_recorded_trace_is_read_soundly():
    got = tracecut.reduce(str(FIXTURE))
    assert got["devices"] == 1
    assert 0 < got["busy_s"] <= got["window_s"]
    assert got["collective_s"] == 0
    ops = got["breakdown"]["device_ops"]
    assert 0 < len(ops) <= tracecut.TOP
    assert [t for _, t in ops] == sorted((t for _, t in ops), reverse=True)
    # leaf operations run one at a time on a core: their sum fits in busy
    assert sum(t for _, t in ops) <= got["busy_s"] * (1 + 1e-9)
    gaps = got["breakdown"]["idle_gaps"]
    assert len(gaps) <= tracecut.TOP
    idle = got["window_s"] - got["busy_s"]
    assert sum(t for _, t in gaps) == pytest.approx(idle, rel=1e-6)
    assert all(n.startswith("chipbench.") or n == "untraced host"
               for n, _ in gaps)


def test_readers_on_the_recorded_trace():
    import harness
    from types import SimpleNamespace
    t = tracecut.reduce(str(FIXTURE))
    run = SimpleNamespace(trace=t, steps=1, chips=1,
                          device_kind="TPU v5 lite",
                          work=lambda: (1e6, 1e3), host={})
    root = harness.ROOT / "metrics"
    idle = harness.load_module(root / "device_idle_pct.py").read(run)
    assert 0 <= idle < 100
    roof = harness.load_module(root / "sweep_roofline_pct.py").read(run)
    assert 0 < roof <= 100
    assert harness.load_module(root / "collective_pct.py").read(run) is None
