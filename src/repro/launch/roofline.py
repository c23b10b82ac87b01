"""Roofline-term extraction from compiled dry-run artifacts.

    compute term    = HLO_FLOPs / (chips × peak_FLOP/s)
    memory term     = HLO_bytes / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

HLO_FLOPs / HLO_bytes come from ``compiled.cost_analysis()`` (per-device on
a partitioned module → × chips for the global figure).  collective_bytes is
parsed from the compiled HLO text: the operand/result bytes of every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute.

Peaks come from ``CHIP_PEAKS``, keyed by ``jax.Device.device_kind``; a
kind that is not in the table is an error, never a default.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field


@dataclass(frozen=True)
class ChipPeaks:
    flops: float          # bf16 FLOP/s
    hbm_bw: float         # HBM bytes/s
    hbm_bytes: float      # HBM capacity
    ici_bw: float         # chip-to-chip bytes/s per link


# Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
# 819 GB/s, 1,600 Gbit/s chip-to-chip interconnect over 4 links
CHIP_PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9,
                             ici_bw=1600e9 / 8 / 4),
}


def chip_peaks(device_kind: str) -> ChipPeaks:
    try:
        return CHIP_PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r};"
                       f" known: {sorted(CHIP_PEAKS)}") from None


COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                  "collective-permute")

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Sum bytes over all tensor types in an HLO type string (handles
    tuples)."""
    total = 0
    for m in _SHAPE_RE.finditer(type_str):
        dtype, dims = m.group(1), m.group(2)
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict[str, float]:
    """Per-collective-kind result bytes, per device (the module is the
    per-device program).  async start/done pairs are counted once (start)."""
    out: dict[str, float] = {k: 0.0 for k in COLLECTIVE_OPS}
    counts: dict[str, int] = {k: 0 for k in COLLECTIVE_OPS}
    for line in hlo_text.splitlines():
        s = line.strip()
        if " = " not in s:
            continue
        lhs, rhs = s.split(" = ", 1)
        for op in COLLECTIVE_OPS:
            # match `<type> op-name(` and async starts; skip `-done`
            if re.match(rf"^[^\s]+\s+{op}(-start)?\(", rhs):
                out[op] += _shape_bytes(rhs.split("(", 1)[0])
                counts[op] += 1
                break
    out_counts = {f"n_{k}": v for k, v in counts.items() if v}
    return {**{k: v for k, v in out.items() if v}, **out_counts}


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    device_kind: str
    flops_per_device: float
    bytes_per_device: float
    coll_bytes_per_device: float
    coll_breakdown: dict = field(default_factory=dict)
    model_flops: float = 0.0
    peak_memory_per_device: float = 0.0

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.coll_bytes_per_device / self.peaks.ici_bw

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def useful_flops_ratio(self) -> float:
        hlo_global = self.flops_per_device * self.chips
        return self.model_flops / hlo_global if hlo_global else 0.0

    @property
    def step_time_s(self) -> float:
        """Roofline step-time estimate: max of the three terms (perfect
        overlap assumption; the no-overlap sum is also reported)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the pure-compute roofline achieved if the step ran at
        the max-term estimate AND all compiled FLOPs were useful model
        FLOPs: (MODEL_FLOPS / chips / peak) / step_time."""
        ideal = self.model_flops / self.chips / self.peaks.flops
        return ideal / self.step_time_s if self.step_time_s else 0.0

    def to_dict(self) -> dict:
        return {
            "arch": self.arch, "shape": self.shape, "mesh": self.mesh,
            "chips": self.chips, "device_kind": self.device_kind,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "coll_bytes_per_device": self.coll_bytes_per_device,
            "coll_breakdown": self.coll_breakdown,
            "model_flops": self.model_flops,
            "peak_memory_per_device": self.peak_memory_per_device,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "bottleneck": self.bottleneck,
            "useful_flops_ratio": self.useful_flops_ratio,
            "step_time_s": self.step_time_s,
            "roofline_fraction": self.roofline_fraction,
        }
