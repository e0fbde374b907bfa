"""Per-chip hardware peaks for the roofline model, keyed by ``device_kind``
(the string JAX reports as ``jax.devices()[0].device_kind``).

A device that is not in the table raises: no peak is ever assumed for it.
"""
from __future__ import annotations

import dataclasses
from typing import Dict


@dataclasses.dataclass(frozen=True)
class ChipPeaks:
    flops_bf16: float      # FLOP/s
    hbm_bw: float          # bytes/s
    ici_link_bw: float     # bytes/s per inter-chip link
    source: str


PEAKS: Dict[str, ChipPeaks] = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 819 GB/s
    # HBM, 1,600 Gbit/s inter-chip interconnect per chip = 200 GB/s over
    # the 4 links of the 2-D torus
    "TPU v5 lite": ChipPeaks(flops_bf16=197e12, hbm_bw=819e9,
                             ici_link_bw=50e9,
                             source='Google Cloud docs, "TPU v5e"'),
}


def peaks(device_kind: str) -> ChipPeaks:
    """The published peaks of ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"sourced entry to repro.roofline.constants.PEAKS (known: "
            f"{sorted(PEAKS)})") from None
