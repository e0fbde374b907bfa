"""Peaks of each chip, and a share of the roofline from a count of work.

The peaks are copied from ``repro.roofline.constants`` with their source, so
that a change to the program cannot move the yardstick.  The counts are each
family's (``bench.families``): *useful* work, worked out from the
configuration's shapes and from the context lengths the window served:
valid positions only, never the kernel's grid or the width of a block
table, and the top-k experts of a token, not every expert a dense MoE path
runs.  So the count is the same whatever implements it, and a share of a
peak cannot pass 100% unless the time is short of the work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Tuple


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float      # FLOP/s
    hbm_bw: float          # bytes/s
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             source='Google Cloud docs, "TPU v5e"'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; KeyError if it is unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"(known: {sorted(PEAKS)})") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   chip: ChipPeaks) -> Tuple[float, str]:
    """Least time the chip could take over the time taken, in %, and which
    bound sets that least time."""
    t_flops = flops / chip.flops_bf16
    t_bytes = nbytes / chip.hbm_bw
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
