"""Collective operations in a reduced device trace."""

ALL_GATHER = "all-gather"


def all_gather_s(run) -> float:
    """Device seconds per chip in operations named as all-gathers
    (``all-gather.3``, ``all-gather-start.3``, ``all-gather-done.3``); 0
    without a trace."""
    t = run.trace
    if not t:
        return 0.0
    return sum(s for name, s in t["ops"].items() if ALL_GATHER in name)
