from repro.roofline.analysis import (CollectiveStats, RooflineReport,
                                     build_report, parse_collectives)
from repro.roofline.analytic import estimate, non_embedding_params
from repro.roofline.constants import PEAKS, ChipPeaks, peaks

__all__ = ["CollectiveStats", "RooflineReport", "build_report",
           "parse_collectives", "estimate", "non_embedding_params",
           "PEAKS", "ChipPeaks", "peaks"]
