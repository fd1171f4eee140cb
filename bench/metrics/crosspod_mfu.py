"""crosspod_mfu: analytic forward and backward FLOPs of the pod steps
completed in the window (``bench/hubert_flops.py``; rematerialisation not
counted), over window seconds x chips x the chip's bf16 peak: the formula
of ``round_mfu``, over the pod steps that the cross-pod entry stamps."""
from bench.metrics.round_mfu import read  # noqa: F401
