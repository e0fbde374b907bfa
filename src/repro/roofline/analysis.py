"""Roofline terms from a compiled dry-run artifact.

compute term    = FLOPs / (chips * peak)
memory term     = bytes / (chips * HBM_bw)
collective term = collective_bytes / (chips * link_bw)

with the peaks of the named ``device_kind`` (``repro.roofline.constants``).

FLOPs/bytes: analytic model (primary — XLA cost_analysis does not scale
while-loop bodies by trip count and our layer loop is a scan) with
cost_analysis reported alongside as a cross-check.

collective_bytes: parsed from the compiled/optimized HLO text — every
all-gather / all-reduce / reduce-scatter / all-to-all / collective-permute
instruction's OUTPUT shape bytes, with instructions inside non-entry
computations (the layer-scan while body) multiplied by n_layers.
"""
from __future__ import annotations

import dataclasses
import json
import re
from typing import Dict, Optional

from repro.configs import InputShape, ModelConfig
from repro.roofline import analytic
from repro.roofline.constants import peaks

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
    "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1, "pred": 1,
    "f8e4m3fn": 1, "f8e5m2": 1,
}

_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of an HLO shape string like 'bf16[2,16,128]{2,1,0}' or a
    tuple '(f32[8,128], f32[8,128])'."""
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


@dataclasses.dataclass
class CollectiveStats:
    bytes_total: float
    by_op: Dict[str, float]
    count: int


def parse_collectives(hlo_text: str, loop_multiplier: int = 1
                      ) -> CollectiveStats:
    """Sum output bytes of collective ops in optimized HLO.

    Instructions living in non-ENTRY computations are assumed to be inside
    the layer-scan while body and are multiplied by ``loop_multiplier``
    (documented assumption: this framework only emits collectives at top
    level or in the per-layer body).
    """
    by_op: Dict[str, float] = {}
    count = 0
    in_entry = False
    for line in hlo_text.splitlines():
        stripped = line.strip()
        if stripped.startswith("ENTRY"):
            in_entry = True
            continue
        if re.match(r"^%?\S+ \(", stripped) and stripped.endswith("{"):
            # new (non-entry) computation definition
            in_entry = False
            continue
        for op in _COLL_OPS:
            # match `= <shape> all-gather(...)` style instructions
            marker = f" {op}("
            alt = f" {op}-start("
            if marker in stripped or alt in stripped:
                lhs = stripped.split("=", 1)
                if len(lhs) != 2:
                    continue
                shape_part = lhs[1].strip().split(op)[0]
                nbytes = _shape_bytes(shape_part)
                mult = 1 if in_entry else loop_multiplier
                by_op[op] = by_op.get(op, 0.0) + nbytes * mult
                count += 1
                break
    return CollectiveStats(sum(by_op.values()), by_op, count)


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    mesh: str
    chips: int
    # terms (seconds)
    t_compute: float
    t_memory: float
    t_collective: float
    dominant: str
    # raw numbers
    flops_analytic: float
    bytes_analytic: float
    model_flops: float
    flops_ratio: float                 # MODEL_FLOPS / analytic FLOPs
    collective_bytes: float
    collective_by_op: Dict[str, float]
    # cross-checks from the compiled artifact
    cost_analysis_flops: Optional[float] = None
    cost_analysis_bytes: Optional[float] = None
    per_device_memory_bytes: Optional[float] = None
    note: str = ""

    def terms(self) -> Dict[str, float]:
        return {"compute": self.t_compute, "memory": self.t_memory,
                "collective": self.t_collective}

    def to_json(self) -> str:
        return json.dumps(dataclasses.asdict(self), indent=1)


def build_report(cfg: ModelConfig, shape: InputShape, mesh_name: str,
                 chips: int, hlo_text: str, device_kind: str,
                 cost: Optional[dict] = None,
                 memory_stats: Optional[dict] = None,
                 note: str = "") -> RooflineReport:
    est = analytic.estimate(cfg, shape)
    coll = parse_collectives(hlo_text, loop_multiplier=cfg.n_layers)
    peak = peaks(device_kind)
    t_c = est.flops / (chips * peak.flops_bf16)
    t_m = est.bytes / (chips * peak.hbm_bw)
    t_x = coll.bytes_total / (chips * peak.ici_link_bw)
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    dominant = max(terms, key=terms.get)
    ca_flops = cost.get("flops") if cost else None
    ca_bytes = cost.get("bytes accessed") if cost else None
    mem = None
    if memory_stats:
        mem = memory_stats.get("bytes")
    return RooflineReport(
        arch=cfg.name, shape=shape.name, mesh=mesh_name, chips=chips,
        t_compute=t_c, t_memory=t_m, t_collective=t_x, dominant=dominant,
        flops_analytic=est.flops, bytes_analytic=est.bytes,
        model_flops=est.model_flops,
        flops_ratio=est.model_flops / max(est.flops, 1.0),
        collective_bytes=coll.bytes_total, collective_by_op=coll.by_op,
        cost_analysis_flops=ca_flops, cost_analysis_bytes=ca_bytes,
        per_device_memory_bytes=mem, note=note)
