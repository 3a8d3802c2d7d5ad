"""``sweep_roofline_pct``: the least time one chip needs for the traced
sweeps' algorithmic work (``work.py``: unpadded rank, float32, CG at its
step bound), at the peak of the chip's ``peaks.json`` row that binds it,
over the chip's device-busy time in the trace."""

import work


def read(run):
    t = run.trace
    if not t or t["busy_s"] <= 0 or run.steps <= 0:
        return None
    b, f = run.work()
    least, _ = work.least_seconds(b * run.steps, f * run.steps,
                                  run.device_kind)
    return 100.0 * least / t["busy_s"]
