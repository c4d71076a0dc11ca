"""Post-compile HLO analysis: collective traffic + roofline terms.

``cost_analysis()`` does not expose collective bytes, so we parse the
compiled (SPMD-partitioned, per-device) HLO text and account each
collective with the standard ring-algorithm cost:

    all-reduce          2 * B * (g-1)/g      bytes on the wire per device
    all-gather          B * (g-1)/g          (B = full/gathered tensor bytes)
    reduce-scatter      B * (g-1)/g
    all-to-all          B * (g-1)/g
    collective-permute  B

Terms (seconds, per the assignment's hardware constants for TPU v5e):

    compute    = flops_per_device / 197e12           (bf16 peak per chip)
    memory     = bytes_per_device / 819e9            (HBM bw per chip)
    collective = wire_bytes_per_device / 50e9        (per-link ICI bw)

cost_analysis numbers are per-device under SPMD, so no chips factor is
needed.
"""

from __future__ import annotations

import dataclasses
import re

__all__ = ["CollectiveStats", "parse_collectives", "roofline_terms", "HW"]


@dataclasses.dataclass(frozen=True)
class HardwareConstants:
    peak_flops: float = 197e12  # bf16 / chip (v5e)
    hbm_bw: float = 819e9  # bytes/s / chip
    ici_bw: float = 50e9  # bytes/s / link
    dcn_bw: float = 3.1e9  # bytes/s / chip (cross-pod share)
    hbm_bytes: float = 16e9  # capacity / chip


HW = HardwareConstants()

_DTYPE_BYTES = {
    "pred": 1, "s4": 1, "u4": 1, "s8": 1, "u8": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8, "c128": 16,
}

_SHAPE_RE = re.compile(r"\b(pred|[sufc]\d+|bf16)\[([0-9,]*)\]")
_GROUPS_V1_RE = re.compile(r"replica_groups=\{\{([0-9,]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]<=")

#: op name -> wire-cost multiplier applied to the *full* tensor bytes
_COLLECTIVES = {
    "all-reduce": 2.0,
    "all-gather": 1.0,
    "reduce-scatter": 1.0,
    "all-to-all": 1.0,
    "collective-permute": 1.0,
    "ragged-all-to-all": 1.0,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


@dataclasses.dataclass
class CollectiveStats:
    per_device_wire_bytes: float
    by_op: dict  # op -> {count, wire_bytes}
    n_ops: int

    def summary(self) -> dict:
        return {
            "wire_bytes_per_device": self.per_device_wire_bytes,
            "n_ops": self.n_ops,
            "by_op": self.by_op,
        }


def parse_collectives(hlo_text: str) -> CollectiveStats:
    total = 0.0
    by_op: dict[str, dict] = {}
    for line in hlo_text.splitlines():
        stripped = line.strip()
        m = re.search(r"=\s*(?:\([^)]*\)|\S+)\s+([a-z0-9-]+)\(", stripped)
        if not m:
            continue
        op = m.group(1)
        base = op.removesuffix("-start")
        if base not in _COLLECTIVES or op.endswith("-done"):
            continue
        shapes = _SHAPE_RE.findall(stripped.split("(", 1)[0])  # result side
        if not shapes:
            shapes = _SHAPE_RE.findall(stripped)
        if not shapes:
            continue
        # Full tensor = the largest shape on the line (gathered side for AG,
        # operand side for RS -- both appear in the HLO text).
        all_shapes = _SHAPE_RE.findall(stripped)
        full = max(_shape_bytes(d, s) for d, s in all_shapes)

        g = None
        m1 = _GROUPS_V1_RE.search(stripped)
        if m1:
            g = len(m1.group(1).split(","))
        else:
            m2 = _GROUPS_IOTA_RE.search(stripped)
            if m2:
                g = int(m2.group(2))
        if not g or g <= 1:
            g = 2  # permutes / unknown: conservative
        ring = (g - 1) / g
        wire = _COLLECTIVES[base] * full * (1.0 if base == "collective-permute" else ring)
        total += wire
        rec = by_op.setdefault(base, {"count": 0, "wire_bytes": 0.0})
        rec["count"] += 1
        rec["wire_bytes"] += wire
    return CollectiveStats(per_device_wire_bytes=total, by_op=by_op, n_ops=sum(r["count"] for r in by_op.values()))


def roofline_terms(
    flops_per_device: float,
    bytes_per_device: float,
    wire_bytes_per_device: float,
    hw: HardwareConstants = HW,
) -> dict:
    compute_s = flops_per_device / hw.peak_flops
    memory_s = bytes_per_device / hw.hbm_bw
    collective_s = wire_bytes_per_device / hw.ici_bw
    terms = {"compute_s": compute_s, "memory_s": memory_s, "collective_s": collective_s}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    total = sum(terms.values())
    return {
        **terms,
        "dominant": dominant,
        "roofline_bound_s": bound,
        "roofline_fraction": bound / total if total else 0.0,
    }
