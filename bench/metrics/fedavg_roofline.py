"""fedavg_roofline: the fedavg_reduce and fedavg_accumulate kernels' share
of their HBM roofline: bytes from the calls' shapes over the peak HBM
bandwidth, over the kernels' device time in the trace."""
from bench.kernels import roofline

PROGRAMS = ("jit_fedavg_reduce", "jit_fedavg_accumulate")


def read(run):
    return roofline(run, "fedavg", PROGRAMS)
