"""Roofline terms from dry-run artifacts (deliverable g).

Hardware model: TPU v5e — 197 TFLOP/s bf16 per chip, 819 GB/s HBM,
~50 GB/s/link ICI (2D torus; ring collectives run along mesh axes).
Inter-pod ("pod" axis) traffic crosses DCI, modeled at 25 GB/s/chip
(documented assumption; the per-axis split comes from the parsed replica
groups).

    compute term    = HLO_FLOPs_per_chip / peak_FLOP/s
    memory term     = HLO_bytes_per_chip / HBM_bw
    collective term = Σ_axis wire_bytes_per_chip(axis) / link_bw(axis)
"""
from __future__ import annotations

from dataclasses import dataclass

from ..kernels.util import SCOPED_VMEM_BYTES
from .hlo_analysis import CellCost


@dataclass(frozen=True)
class HardwareSpec:
    name: str = "tpu-v5e"
    peak_bf16: float = 197e12        # FLOP/s per chip
    hbm_bw: float = 819e9            # B/s per chip
    ici_bw: float = 50e9             # B/s per link (per mesh axis)
    dci_bw: float = 25e9             # B/s per chip across pods (assumption)
    hbm_bytes: float = 16e9          # v5e HBM capacity
    vmem_bytes: float = SCOPED_VMEM_BYTES  # VMEM the compiler lets one
    #                                        Pallas program use (of 128 MiB)
    mxu: int = 128                   # systolic array edge (MXU 128×128)

    @classmethod
    def from_cluster(cls, spec) -> "HardwareSpec":
        """Roofline view of a ClusterSpec: intra-pod links from the
        'model' level's β, the cross-pod hop from 'pod' — so dry-run
        rooflines and oracle projections read one machine description.
        VMEM/MXU keep the v5e defaults (ClusterSpec models the machine at
        HBM/interconnect granularity; the kernel autotuner consumes them
        through this view — kernels/autotune/space.py)."""
        return cls(name=spec.name, peak_bf16=spec.peak_flops,
                   hbm_bw=spec.hbm_bw,
                   ici_bw=1.0 / spec.level("model").beta,
                   dci_bw=1.0 / spec.level("pod").beta,
                   hbm_bytes=spec.mem_capacity)


V5E = HardwareSpec()


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    collective_by_axis: dict
    model_flops: float              # 6·N·tokens (or 2·N for inference)
    hlo_flops_total: float          # per-chip × chips
    chips: int
    temp_bytes: int
    fits_hbm: bool
    kind: str = "train"
    arg_bytes: int = 0

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_ratio(self) -> float:
        """MODEL_FLOPS / HLO_FLOPs — remat/redundancy/dispatch waste."""
        return self.model_flops / self.hlo_flops_total if self.hlo_flops_total else 0.0

    @property
    def serial_s(self) -> float:
        """Fully-serial upper bound: compute + memory + collectives, nothing
        hidden — the paper's accounting."""
        return self.compute_s + self.memory_s + self.collective_s

    @property
    def step_time_s(self) -> float:
        """Full-overlap lower bound: max of the three terms."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    def overlapped_s(self, sigma: float = 0.8) -> float:
        """Overlap-model estimate between the two bounds (DESIGN.md §10):
        collectives hide under the on-chip work with efficiency σ —
        T = max(T_chip, T_coll) + (1−σ)·min(T_chip, T_coll), where T_chip
        is the compute/HBM bound max(compute_s, memory_s). σ=1 recovers
        ``step_time_s``; σ=0 charges collectives serially."""
        chip = max(self.compute_s, self.memory_s)
        return max(chip, self.collective_s) \
            + (1.0 - sigma) * min(chip, self.collective_s)

    @property
    def ideal_s(self) -> float:
        """Per-kind ideal step time: compute-bound for train/prefill,
        memory-bound (stream params+cache once) for decode."""
        compute_ideal = self.model_flops / self.chips / V5E.peak_bf16
        if self.kind == "decode":
            return max(self.arg_bytes / V5E.hbm_bw, compute_ideal)
        return compute_ideal

    @property
    def roofline_fraction(self) -> float:
        """Achievable bound: ideal step time / bound step time."""
        return self.ideal_s / self.step_time_s if self.step_time_s else 0.0

    def to_json(self, sigma: float = 0.8) -> dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "collective_by_axis": self.collective_by_axis,
            "dominant": self.dominant,
            "model_flops": self.model_flops,
            "hlo_flops_total": self.hlo_flops_total,
            "useful_ratio": self.useful_ratio,
            "step_time_bound_s": self.step_time_s,
            "serial_s": self.serial_s,
            "overlapped_s": self.overlapped_s(sigma),
            "overlap_sigma": sigma,      # which σ the field above assumed
            "ideal_s": self.ideal_s,
            "roofline_fraction": self.roofline_fraction,
            "temp_bytes": self.temp_bytes,
            "fits_hbm": self.fits_hbm,
            "chips": self.chips,
        }


def roofline(cost: CellCost, chips: int, model_flops: float,
             hw: HardwareSpec = V5E, kind: str = "train") -> Roofline:
    by_axis = {}
    coll_total = 0.0
    for axis in ("pod", "data", "model", "mixed", "none"):
        # native-dtype accounting: fp32 payloads that are CPU-lowering
        # artifacts of bf16 dots count at bf16 width (the TPU reality)
        wire = cost.wire_bytes(axis, native_dtype=True)
        bw = hw.dci_bw if axis == "pod" else hw.ici_bw
        t = wire / bw
        if wire:
            by_axis[axis] = {"wire_bytes": wire, "seconds": t}
        coll_total += t
    state_bytes = cost.arg_bytes  # params + opt state + cache live in HBM
    return Roofline(
        compute_s=cost.flops / hw.peak_bf16,
        memory_s=cost.bytes_accessed / hw.hbm_bw,
        collective_s=coll_total,
        collective_by_axis=by_axis,
        model_flops=model_flops,
        hlo_flops_total=cost.flops * chips,
        chips=chips,
        temp_bytes=cost.temp_bytes,
        kind=kind, arg_bytes=cost.arg_bytes,
        fits_hbm=(cost.temp_bytes + state_bytes) <= hw.hbm_bytes)
