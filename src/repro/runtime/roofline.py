"""Roofline analysis from compiled dry-run artifacts.

Hardware model: per-chip peaks keyed by ``jax.Device.device_kind``
(``PEAKS``); a device kind missing from the table is an error, never a
default.

  compute term    = HLO_FLOPs            / (chips * peak FLOP/s)
  memory term     = HLO_bytes            / (chips * HBM bytes/s)
  collective term = collective_bytes     / (chips * ICI bytes/s/link)

``cost_analysis()`` supplies FLOPs/bytes; collective bytes are parsed
out of the (post-SPMD) compiled HLO text by summing output operand sizes
of every all-gather / all-reduce / reduce-scatter / all-to-all /
collective-permute op.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, asdict
from typing import Dict, Optional



@dataclass(frozen=True)
class ChipPeaks:
    flops: float               # bf16 FLOP/s per chip
    hbm_bw: float              # HBM bytes/s per chip
    ici_bw: float              # ICI bytes/s per link


#: Published per-chip peaks by ``device_kind``.  TPU v5e ("TPU v5
#: lite"): Google Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16,
#: 819 GB/s HBM, 1,600 Gbit/s of ICI per chip over 4 links (50 GB/s
#: per link).
PEAKS = {
    "TPU v5 lite": ChipPeaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}

#: the chip the dry-run and probe launchers model their production
#: meshes on (they compile on the host, so no device reports a kind)
TARGET_DEVICE_KIND = "TPU v5 lite"


def chip_peaks(device_kind: str) -> ChipPeaks:
    """Peaks of ``device_kind``; raises for a chip not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r}; known: {sorted(PEAKS)}") from None

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> int:
    if dtype not in _DTYPE_BYTES:
        return 0
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES[dtype]


def collective_bytes(hlo_text: str) -> Dict[str, int]:
    """Sum output bytes per collective kind over the compiled module.

    HLO lines look like ``%all-reduce.119 = f32[16,256,49155]{2,1,0}
    all-reduce(%x), ...`` — the *op* is the token on the right-hand side
    of ``=``; the left-hand side is the instruction name (which may also
    contain the op string), so we only scan the RHS.
    """
    out = {k: 0 for k in _COLLECTIVES}
    out["count"] = 0
    for line in hlo_text.splitlines():
        if " = " not in line:
            continue
        rhs = line.split(" = ", 1)[1]
        for kind in _COLLECTIVES:
            pos = rhs.find(f" {kind}(")
            if pos < 0:
                pos = rhs.find(f" {kind}-start(")
            if pos < 0:
                continue
            head = rhs[:pos + 1]
            nbytes = sum(_shape_bytes(dt, dims)
                         for dt, dims in _SHAPE_RE.findall(head))
            out[kind] += nbytes
            out["count"] += 1
            break
    return out


@dataclass
class RooflineTerms:
    arch: str
    shape: str
    mesh: str
    chips: int
    hlo_flops: float
    hlo_bytes: float
    coll_bytes: float
    coll_breakdown: Dict[str, int]
    model_flops: float = 0.0           # 6*N(active)*D
    bytes_per_device: float = 0.0      # from memory_analysis
    device_kind: str = TARGET_DEVICE_KIND

    @property
    def peaks(self) -> ChipPeaks:
        return chip_peaks(self.device_kind)

    @property
    def compute_s(self) -> float:
        return self.hlo_flops / (self.chips * self.peaks.flops)

    @property
    def memory_s(self) -> float:
        return self.hlo_bytes / (self.chips * self.peaks.hbm_bw)

    @property
    def collective_s(self) -> float:
        return self.coll_bytes / (self.chips * self.peaks.ici_bw)

    @property
    def bottleneck(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Roofline step-time estimate = max of the three terms (perfect
        overlap assumption)."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def useful_flops_ratio(self) -> float:
        return self.model_flops / self.hlo_flops if self.hlo_flops else 0.0

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the compute roofline achieved at the estimated step
        time (== MFU bound when compute-dominated)."""
        if self.step_time_s == 0:
            return 0.0
        return ((self.model_flops / self.step_time_s) /
                (self.chips * self.peaks.flops))

    def to_dict(self) -> dict:
        d = asdict(self)
        d.update(compute_s=self.compute_s, memory_s=self.memory_s,
                 collective_s=self.collective_s, bottleneck=self.bottleneck,
                 step_time_s=self.step_time_s,
                 useful_flops_ratio=self.useful_flops_ratio,
                 roofline_fraction=self.roofline_fraction)
        return d


def analyze(compiled, lowered_text: Optional[str], arch: str, shape: str,
            mesh_name: str, chips: int, model_flops: float,
            device_kind: str = TARGET_DEVICE_KIND) -> RooflineTerms:
    chip_peaks(device_kind)
    cost = compiled.cost_analysis()
    flops = float(cost.get("flops", 0.0))
    nbytes = float(cost.get("bytes accessed", 0.0))
    text = compiled.as_text() if lowered_text is None else lowered_text
    coll = collective_bytes(text)
    total_coll = sum(v for k, v in coll.items() if k != "count")
    mem = compiled.memory_analysis()
    bpd = 0.0
    if mem is not None:
        bpd = (getattr(mem, "argument_size_in_bytes", 0) +
               getattr(mem, "output_size_in_bytes", 0) +
               getattr(mem, "temp_size_in_bytes", 0))
    return RooflineTerms(arch=arch, shape=shape, mesh=mesh_name, chips=chips,
                         hlo_flops=flops, hlo_bytes=nbytes,
                         coll_bytes=float(total_coll), coll_breakdown=coll,
                         model_flops=model_flops, bytes_per_device=bpd,
                         device_kind=device_kind)


def model_flops_estimate(n_active_params: int, tokens: int,
                         kind: str) -> float:
    """6*N*D for training; 2*N*D for inference forward."""
    if kind == "train":
        return 6.0 * n_active_params * tokens
    return 2.0 * n_active_params * tokens
