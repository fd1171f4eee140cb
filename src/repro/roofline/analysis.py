"""3-term roofline from a compiled dry-run artifact, against the peaks
of a named device kind (``PEAKS``).

compute term    = HLO_FLOPs / (chips * peak_FLOP/s)
memory term     = HLO_bytes / (chips * HBM_bw)
collective term = collective_bytes / (chips * link_bw)   [ICI]
                  (+ DCN term reported separately for multi-pod)

HLO_FLOPs / collective_bytes come from the trip-count-aware HLO walk
(hlo_cost.py) over ``compiled.as_text()`` — the SPMD module is the
per-chip program, so terms divide only by per-chip peak rates.

Memory term: the CPU backend's fusion/copy structure differs from TPU
(XLA:CPU materialises loop-carried copies a TPU program would alias), so
raw HLO operand-byte sums overstate HBM traffic by >10x. Instead the
memory term uses the compiled buffer inventory from
``compiled.memory_analysis()``: every live buffer written once + read once
(args + outputs + 2*temps). The raw HLO-walk bytes are kept in the record
as ``hlo_walk_bytes`` (diagnostic upper bound). Both derive from the
compiled dry-run artifact.
"""
from __future__ import annotations

import dataclasses
import json
from typing import Optional

from repro.roofline.hlo_cost import Cost, entry_cost


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""
    flops: float  # bf16 FLOP/s
    hbm_bw: float  # bytes/s
    ici_bw: float  # bytes/s per link


# Keyed by ``jax.Device.device_kind``. TPU v5e: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s HBM); the ICI figure is the
# per-link share used for the collective term.
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}
DCN_BW = 6.25e9  # bytes/s per host across pods


def peaks_for(device_kind: str) -> ChipPeaks:
    """The table row for ``device_kind``; a kind not in it is an error."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


@dataclasses.dataclass
class Roofline:
    arch: str
    shape: str
    kind: str
    mesh: str
    chips: int
    device_kind: str
    hlo_flops: float
    hlo_bytes: float  # buffer-inventory traffic (args + outputs + 2*temps)
    hlo_walk_bytes: float  # raw HLO operand-byte walk (diagnostic)
    coll_ici_bytes: float
    coll_dcn_bytes: float
    coll_by_op: dict
    model_flops: float  # 6*N(_active)*tokens for train, 2*N for fwd-only
    # seconds
    t_compute: float = 0.0
    t_memory: float = 0.0
    t_collective: float = 0.0
    t_dcn: float = 0.0

    def finalize(self):
        peaks = peaks_for(self.device_kind)
        self.t_compute = self.hlo_flops / peaks.flops
        self.t_memory = self.hlo_bytes / peaks.hbm_bw
        self.t_collective = self.coll_ici_bytes / peaks.ici_bw
        self.t_dcn = self.coll_dcn_bytes / DCN_BW
        return self

    @property
    def dominant(self) -> str:
        terms = {"compute": self.t_compute, "memory": self.t_memory,
                 "collective": self.t_collective, "dcn": self.t_dcn}
        return max(terms, key=terms.get)

    @property
    def bound_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective,
                   self.t_dcn)

    @property
    def useful_flops_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs (catches remat/dispatch waste).
        Program is per-chip, MODEL_FLOPS is global -> divide by chips."""
        per_chip_model = self.model_flops / self.chips
        return per_chip_model / max(self.hlo_flops, 1.0)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved if the program ran at
        its bound: (useful flops / peak) / bound_time."""
        per_chip_model = self.model_flops / self.chips
        ideal = per_chip_model / peaks_for(self.device_kind).flops
        return ideal / max(self.bound_time, 1e-30)

    def to_dict(self):
        d = dataclasses.asdict(self)
        d.update(dominant=self.dominant, bound_time=self.bound_time,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def model_flops_for(cfg, shape) -> float:
    """MODEL_FLOPS convention: 6*N*D for training; 2*N*D forward-only
    (prefill); 2*N_active per token for decode."""
    from repro.models.registry import active_param_count

    n_active = active_param_count(cfg)
    tokens = shape.tokens_per_step
    if cfg.family == "audio":  # seq_len counts samples; the tokens are frames
        from repro.models.hubert import frames_for
        tokens = shape.global_batch * frames_for(cfg, shape.seq_len)
    if shape.kind == "train":
        return 6.0 * n_active * tokens
    return 2.0 * n_active * tokens


def analyze(compiled, *, arch: str, shape, kind: str, mesh_name: str,
            chips: int, pod_size: int, cfg, device_kind: str) -> Roofline:
    cost = entry_cost(compiled.as_text(), pod_size=pod_size)
    mem = compiled.memory_analysis()
    traffic = (mem.argument_size_in_bytes + mem.output_size_in_bytes
               + 2 * mem.temp_size_in_bytes)
    rl = Roofline(
        arch=arch, shape=shape.name, kind=kind, mesh=mesh_name, chips=chips,
        device_kind=device_kind, hlo_flops=cost.flops,
        hlo_bytes=float(traffic), hlo_walk_bytes=cost.hbm_bytes,
        coll_ici_bytes=cost.coll_ici_bytes,
        coll_dcn_bytes=cost.coll_dcn_bytes, coll_by_op=cost.coll_by_op,
        model_flops=model_flops_for(cfg, shape))
    return rl.finalize()
