"""Peaks of each chip and the work the served tokens need.

The peaks are copied from ``repro.roofline.constants`` with their source, so
that a change to the program cannot move the yardstick.  Every count below
is of *useful* work, worked out from the configuration's shapes and from the
context lengths the window served: valid positions only, never the kernel's
grid or the width of a block table, and the top-k experts of a token, not
every expert a dense MoE path runs.  So the count is the same whatever
implements it, and a share of a peak cannot pass 100% unless the time is
short of the work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, Tuple


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


_DTYPE_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1}


def layer_matmul_params(cfg: dict) -> int:
    """Weights one token multiplies through in one layer: attention
    projections plus the FFN of the experts it is routed to (and the
    router) — the active parameters, not the stored ones."""
    d, dh = cfg["d_model"], cfg["d_head"]
    attn = d * cfg["n_heads"] * dh * 2 + d * cfg["n_kv_heads"] * dh * 2
    ffn = 3 * d * cfg["d_ff"]
    moe = cfg.get("moe")
    if moe:
        return attn + moe["top_k"] * ffn + d * moe["n_experts"]
    return attn + ffn


def token_flops(cfg: dict, attended: int, head: bool) -> float:
    """Forward FLOPs of one token that attends ``attended`` positions
    (itself included); ``head`` adds the LM head, which a prefilled prompt
    token does not run."""
    L, H, dh = cfg["n_layers"], cfg["n_heads"], cfg["d_head"]
    f = 2.0 * L * layer_matmul_params(cfg) + 4.0 * L * H * dh * attended
    if head:
        f += 2.0 * cfg["d_model"] * cfg["vocab_size"]
    return f


def chunk_flops(cfg: dict, start: int, length: int) -> float:
    """FLOPs of prefilling prompt positions [start, start + length)."""
    L, H, dh = cfg["n_layers"], cfg["n_heads"], cfg["d_head"]
    # token j attends start + j + 1 positions (causal, itself included)
    attended = length * start + length * (length + 1) / 2.0
    return (2.0 * L * layer_matmul_params(cfg) * length
            + 4.0 * L * H * dh * attended)


def _kv_bytes(cfg: dict) -> int:
    return _DTYPE_BYTES[cfg.get("kv_cache_dtype", "bfloat16")]


def decode_attention_work(cfg: dict, cached: Iterable[int]
                          ) -> Tuple[float, float]:
    """(FLOPs, bytes) of the paged decode kernel over all layers for one
    step, given the cached positions each decoding row reads (the current
    token's own column is merged outside the kernel).  Bytes: K and V of
    the valid positions, the float32 query and the float32 partials out."""
    L, H, KV, dh = (cfg["n_layers"], cfg["n_heads"], cfg["n_kv_heads"],
                    cfg["d_head"])
    flops = nbytes = 0.0
    for p in cached:
        flops += 4.0 * H * dh * p
        nbytes += 2.0 * KV * dh * p * _kv_bytes(cfg) + 4.0 * (2 * H * dh
                                                              + 2 * H)
    return L * flops, L * nbytes


def roofline_share(flops: float, nbytes: float, seconds: float,
                   chip: ChipPeaks) -> Tuple[float, str]:
    """Least time the chip could take over the time taken, in %, and which
    bound sets that least time."""
    t_flops = flops / chip.flops_bf16
    t_bytes = nbytes / chip.hbm_bw
    bound = "compute" if t_flops >= t_bytes else "memory"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
