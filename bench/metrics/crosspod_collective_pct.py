"""crosspod_collective_pct: share of the chips' busy time spent in the
all-gather operations of the cross-pod exchange, from the device trace
(per chip means over the cell's chips)."""
from bench.collectives import all_gather_s


def read(run):
    t = run.trace
    secs = all_gather_s(run)
    if not secs or t["busy_s"] <= 0:
        return None
    return 100.0 * secs / t["busy_s"]
