"""``collective_pct``: share of device-busy time spent in collective
operations (all-reduce, all-gather, reduce-scatter, all-to-all, collective
permute), from the trace, averaged over the chips. Nothing to read on one
chip."""


def read(run):
    t = run.trace
    if not t or run.chips < 2 or t["busy_s"] <= 0:
        return None
    return 100.0 * t["collective_s"] / t["busy_s"]
