"""exchange_roofline: the cross-pod exchange's share of its interconnect
roofline: the int8 bytes each chip receives in the rounds completed in
the window (from the program's ``crosspod.exchange_bytes``) over the
chip's peak inter-chip bandwidth, over the all-gather operations' device
time per chip in the trace."""
from bench.collectives import all_gather_s


def read(run):
    bw = run.peaks.get("ici_bits_per_s")
    secs = all_gather_s(run)
    nbytes = sum(b for at, g, b in run.kernel_calls
                 if g == "exchange" and run.in_window(at))
    if not bw or not secs or not nbytes:
        return None
    return 100.0 * nbytes / (bw / 8) / secs
