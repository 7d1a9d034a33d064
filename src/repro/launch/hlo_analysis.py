"""Roofline-term extraction from compiled dry-run artifacts.

collective_bytes is NOT in cost_analysis(): we parse the optimized HLO
text and sum the result sizes of every collective op, weighted by its
wire pattern (all-reduce moves ~2x its payload on a ring; reduce-scatter
and all-gather ~1x; all-to-all and collective-permute 1x).

Hardware peaks are keyed by ``device_kind`` (``PEAKS``); a kind with no
entry is an error, not a default.
"""
from __future__ import annotations

import dataclasses
import re


@dataclasses.dataclass(frozen=True)
class Peaks:
    flops: float      # bf16 FLOP/s per chip
    hbm_bw: float     # HBM bytes/s per chip
    ici_bw: float     # interconnect bytes/s per link


PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM, 1,600 Gbit/s of interconnect per chip over 4 links
    "TPU v5 lite": Peaks(flops=197e12, hbm_bw=819e9, ici_bw=50e9),
}


def peaks_for(device_kind: str) -> Peaks:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add "
            f"them to hlo_analysis.PEAKS with their source") from None

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "token": 0, "s4": 1, "u4": 1,
}

_COLLECTIVES = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute", "collective-broadcast", "ragged-all-to-all",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO shape string."""
    total = 0
    for m in _SHAPE_RE.finditer(shape_str):
        dt, dims = m.group(1), m.group(2)
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind result bytes summed over the module.

    Returns {kind: bytes, "total_wire": weighted bytes} where total_wire
    applies the ring-cost weighting described in the module docstring.
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        # form: "%name = <shape> all-gather(...)" or "ROOT %x = <shape> op("
        m = re.search(r"=\s*(\([^)]*\)|\S+)\s+([\w-]+)", stripped)
        if not m:
            continue
        shape_str, op = m.group(1), m.group(2)
        base = None
        for k in _COLLECTIVES:
            if op == k or op.startswith(k + "-"):  # e.g. all-gather-start
                base = k
                break
        if base is None:
            continue
        if op.endswith("-done"):
            continue  # avoid double counting async pairs
        out[base] += _shape_bytes(shape_str)
    weights = {
        "all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
        "all-to-all": 1.0, "collective-permute": 1.0,
        "collective-broadcast": 1.0, "ragged-all-to-all": 1.0,
    }
    out["total_wire"] = sum(out[k] * weights[k] for k in _COLLECTIVES)
    return out


@dataclasses.dataclass
class Roofline:
    flops_per_device: float
    hbm_bytes_per_device: float
    collective_wire_bytes: float
    bytes_by_kind: dict
    peaks: Peaks

    @property
    def compute_s(self) -> float:
        return self.flops_per_device / self.peaks.flops

    @property
    def memory_s(self) -> float:
        return self.hbm_bytes_per_device / self.peaks.hbm_bw

    @property
    def collective_s(self) -> float:
        return self.collective_wire_bytes / self.peaks.ici_bw

    @property
    def dominant(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    def to_dict(self) -> dict:
        return {
            "flops_per_device": self.flops_per_device,
            "hbm_bytes_per_device": self.hbm_bytes_per_device,
            "collective_wire_bytes": self.collective_wire_bytes,
            "compute_s": self.compute_s,
            "memory_s": self.memory_s,
            "collective_s": self.collective_s,
            "dominant": self.dominant,
            "bytes_by_kind": {k: v for k, v in self.bytes_by_kind.items()},
        }


def analyze(compiled, device_kind: str) -> Roofline:
    """Roofline terms of ``compiled`` on a chip of ``device_kind``."""
    cost = compiled.cost_analysis()
    cb = collective_bytes(compiled.as_text())
    return Roofline(
        flops_per_device=float(cost.get("flops", 0.0)),
        hbm_bytes_per_device=float(cost.get("bytes accessed", 0.0)),
        collective_wire_bytes=float(cb["total_wire"]),
        bytes_by_kind=cb,
        peaks=peaks_for(device_kind),
    )
