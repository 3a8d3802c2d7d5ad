"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
benchmark reports.

* Device operations are the events on each device plane's ``XLA Ops``
  line. A device is busy where at least one of them runs: the union of
  their intervals, clipped to the window. An event's name is the HLO
  instruction's text; an operation is named by its instruction name and
  result type (``fusion.110 f32[3139928,32]``). Control-flow operations
  (``while``, ``conditional``, ``call``) span the operations they run, so
  the list of operations that took most time leaves them out.
* The window is the benchmark's own host span ``chipbench.window`` where
  the trace holds it, and otherwise the span from the first device
  operation to the end of the last.
* Collective operations are those whose name names an all-reduce,
  all-gather, reduce-scatter, all-to-all or collective permute; their
  time is the union of their intervals.
* Idle gaps are the stretches of the window in which a device runs
  nothing. Each is named by the innermost ``chipbench.*`` host span that
  covers its middle, or ``untraced host`` where none does.

Busy and collective seconds are averaged over the device planes.
"""
from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "chipbench.window"
SPAN_PREFIX = "chipbench."
COLLECTIVE = re.compile(
    r"all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"allreduce|allgather|reducescatter|alltoall", re.IGNORECASE)
TOP = 10
CONTAINERS = ("while", "conditional", "call")


def find_xplane(directory: str) -> str:
    files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return max(files, key=os.path.getmtime)


def op_name(text: str) -> str:
    """``fusion.110 f32[3139928,32]`` from an HLO instruction's text."""
    name, _, rest = text.partition(" = ")
    out = "" if rest.startswith("(") else rest.split("{")[0].split(" ")[0]
    return f"{name.lstrip('%')} {out}".strip()


def _union(intervals, lo=None, hi=None) -> list:
    """Merged, sorted ``[start, end]`` intervals, clipped to ``[lo, hi]``."""
    out = []
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _length(merged) -> float:
    return float(sum(e - s for s, e in merged))


def planes(path: str):
    """``(device planes, host spans)``: for each device plane with an ops
    line, its name and its ``(name, start_ns, end_ns)`` operations; and
    every ``chipbench.*`` host span as ``(name, start_ns, end_ns)``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            ops = [(op_name(ev.name), float(ev.start_ns), float(ev.end_ns))
                   for line in plane.lines if line.name == OPS_LINE
                   for ev in line.events]
            if ops:
                devices.append((plane.name, ops))
        elif plane.name.startswith("/host:"):
            spans.extend((ev.name, float(ev.start_ns), float(ev.end_ns))
                         for line in plane.lines for ev in line.events
                         if ev.name.startswith(SPAN_PREFIX))
    return devices, spans


def reduce(path: str) -> dict:
    """The trace's ``busy_s``, ``window_s``, ``collective_s`` (per device,
    averaged), ``devices`` and ``breakdown`` (top device operations by
    time, longest idle gaps by host span)."""
    devices, spans = planes(path)
    if not devices:
        raise ValueError(f"{path}: no device plane with an '{OPS_LINE}' line")
    windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for _, ops in devices for _, s, _ in ops)
        hi = max(e for _, ops in devices for _, _, e in ops)
    busy, coll = [], []
    op_time = defaultdict(float)
    gaps = []
    for _, ops in devices:
        merged = _union([(s, e) for _, s, e in ops], lo, hi)
        busy.append(_length(merged))
        coll.append(_length(_union([(s, e) for n, s, e in ops
                                    if COLLECTIVE.search(n)], lo, hi)))
        for n, s, e in ops:
            if not n.startswith(CONTAINERS):
                op_time[n] += max(0.0, min(e, hi) - max(s, lo))
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    n_dev = len(devices)
    named_gaps = defaultdict(float)
    for s, e in gaps:
        named_gaps[_cover(spans, (s + e) / 2)] += (e - s) / n_dev
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:TOP]
    gaps_top = sorted(named_gaps.items(), key=lambda kv: -kv[1])[:TOP]
    return {
        "busy_s": sum(busy) / n_dev * 1e-9,
        "window_s": (hi - lo) * 1e-9,
        "collective_s": sum(coll) / n_dev * 1e-9,
        "devices": n_dev,
        "breakdown": {
            "device_ops": [[n, t / n_dev * 1e-9] for n, t in ops_top],
            "idle_gaps": [[n, t * 1e-9] for n, t in gaps_top]},
    }


def _cover(spans, t: float) -> str:
    best = None
    for n, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[1] - best[0]):
            best = (s, e, n)
    return best[2] if best else "untraced host"


def reduce_dir(directory: str) -> dict:
    return reduce(find_xplane(directory))
