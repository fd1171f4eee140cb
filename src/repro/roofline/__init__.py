from repro.roofline.analysis import (DCN_BW, PEAKS, ChipPeaks, Roofline,
                                     analyze, model_flops_for, peaks_for)
from repro.roofline.hlo_cost import Cost, entry_cost

__all__ = ["analyze", "Roofline", "entry_cost", "Cost", "model_flops_for",
           "PEAKS", "ChipPeaks", "peaks_for", "DCN_BW"]
