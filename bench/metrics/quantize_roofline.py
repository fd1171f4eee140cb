"""quantize_roofline: the quantize_blocks and dequantize_blocks kernels'
share of their HBM roofline: bytes from the calls' shapes over the peak
HBM bandwidth, over the kernels' device time in the trace."""
from bench.kernels import roofline

PROGRAMS = ("jit_quantize_blocks", "jit_dequantize_blocks")


def read(run):
    return roofline(run, "quantize", PROGRAMS)
