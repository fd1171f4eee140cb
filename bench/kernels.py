"""A kernel's share of its roofline, from the trace and its call shapes."""


def roofline(run, group: str, programs) -> float:
    """Bytes of the ``group`` calls made in the window over the peak HBM
    bandwidth, over the device time of the programs named ``programs``;
    None where the window holds no such call."""
    t = run.trace
    bw = run.peaks.get("hbm_bytes_per_s")
    if not t or not bw:
        return None
    nbytes = sum(b for at, g, b in run.kernel_calls
                 if g == group and run.in_window(at))
    secs = sum(s for name, (_, s) in t["modules"].items()
               if name.startswith(programs))
    if not nbytes or secs <= 0:
        return None
    return 100.0 * nbytes / bw / secs
