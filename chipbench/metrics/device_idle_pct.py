"""``device_idle_pct``: share of the traced window in which the device ran
no operation, averaged over the chips: ``100 * (1 - busy / window)``."""


def read(run):
    t = run.trace
    if not t or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
