"""Three-term roofline from a compiled dry-run artifact.

Hardware constants come from one table keyed by the chip's ``device_kind``
(:data:`PEAKS`); a chip that is not in it is an error, never a default.
The dry-run models the production v5e mesh, so a :class:`Roofline` prices
its terms at ``peaks(V5E)``.

Terms (seconds, per step):
    compute    = HLO_FLOPs_per_chip / peak
    memory     = HLO_bytes_per_chip / hbm_bw
    collective = collective_bytes_per_chip / link_bw

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) per train step
(3x forward-only for serve steps); the ratio MODEL_FLOPS/HLO_FLOPs exposes
remat/redundancy waste.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import ClassVar


@dataclass(frozen=True)
class ChipPeaks:
    """Published per-chip peaks."""

    flops: float  # bf16 FLOP/s
    hbm_bw: float  # HBM bytes/s
    link_bw: float  # ICI bytes/s per link


V5E = "TPU v5 lite"  # jax Device.device_kind of a TPU v5e chip

# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM at
# 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect (4 links x 50 GB/s)
PEAKS = {
    V5E: ChipPeaks(flops=197e12, hbm_bw=819e9, link_bw=50e9),
}


def peaks(device_kind: str) -> ChipPeaks:
    """Peaks of the chip named by ``device_kind``; unknown chips raise."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; "
            f"known: {sorted(PEAKS)}"
        ) from None


@dataclass
class Roofline:
    arch: str
    shape: str
    mesh: str
    n_chips: int
    flops_per_chip: float
    bytes_per_chip: float
    collective_bytes_per_chip: float
    model_flops_total: float
    hbm_bytes_est_per_chip: float = 0.0
    chip: ClassVar[ChipPeaks] = peaks(V5E)

    @property
    def t_compute(self) -> float:
        return self.flops_per_chip / self.chip.flops

    @property
    def t_memory(self) -> float:
        return self.bytes_per_chip / self.chip.hbm_bw

    @property
    def t_collective(self) -> float:
        return self.collective_bytes_per_chip / self.chip.link_bw

    @property
    def t_memory_est(self) -> float:
        """Fusion-aware HBM-traffic estimate (see roofline/hlo.py); the raw
        cost_analysis bytes (t_memory) are an unfused upper bound on CPU."""
        return self.hbm_bytes_est_per_chip / self.chip.hbm_bw

    @property
    def bottleneck_est(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory_est,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_est(self) -> float:
        return max(self.t_compute, self.t_memory_est, self.t_collective)

    @property
    def mfu_est(self) -> float:
        """MODEL_FLOPS / (chips * peak * step_est): the roofline fraction with
        the fusion-aware memory term."""
        denom = self.n_chips * self.chip.flops * self.step_time_est
        return self.model_flops_total / denom if denom else 0.0

    @property
    def bottleneck(self) -> str:
        terms = {
            "compute": self.t_compute,
            "memory": self.t_memory,
            "collective": self.t_collective,
        }
        return max(terms, key=terms.get)

    @property
    def step_time_lower_bound(self) -> float:
        """Perfect-overlap model: step >= max(terms)."""
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def useful_flops_fraction(self) -> float:
        """MODEL_FLOPS / (HLO_FLOPs across all chips)."""
        total_hlo = self.flops_per_chip * self.n_chips
        return self.model_flops_total / total_hlo if total_hlo else 0.0

    @property
    def mfu_upper_bound(self) -> float:
        """MODEL_FLOPS / (chips * peak * step_lower_bound): the roofline
        fraction achievable if the step ran exactly at its dominant term."""
        denom = self.n_chips * self.chip.flops * self.step_time_lower_bound
        return self.model_flops_total / denom if denom else 0.0

    def row(self) -> dict:
        return {
            "arch": self.arch,
            "shape": self.shape,
            "mesh": self.mesh,
            "chips": self.n_chips,
            "t_compute_s": round(self.t_compute, 6),
            "t_memory_s": round(self.t_memory, 6),
            "t_collective_s": round(self.t_collective, 6),
            "t_memory_est_s": round(self.t_memory_est, 6),
            "bottleneck": self.bottleneck,
            "bottleneck_est": self.bottleneck_est,
            "model_flops": f"{self.model_flops_total:.3e}",
            "hlo_flops_per_chip": f"{self.flops_per_chip:.3e}",
            "useful_flops_frac": round(self.useful_flops_fraction, 4),
            "mfu_upper_bound": round(self.mfu_upper_bound, 4),
            "mfu_est": round(self.mfu_est, 4),
        }


def model_flops(arch, shape) -> float:
    """6*N*D train / 2*N*D forward-only, with N = active params (MoE-aware)."""
    n_active = arch.param_count(active_only=True)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_active * tokens
    # decode: one token per sequence
    return 2.0 * n_active * shape.global_batch
