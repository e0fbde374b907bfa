"""jit'd public wrappers for the Pallas kernels.

``interpret`` defaults to True off-TPU (the kernel bodies execute in
interpret mode, which is how correctness is validated on CPU) and to False
on TPU, where the Mosaic-compiled kernels are the production hot path.
``REPRO_PALLAS_INTERPRET=1|0`` overrides the autodetection off-TPU — CI's
kernel-parity job forces ``1`` so the fused serving step is exercised
through the Pallas machinery on every PR.  On a TPU backend interpret mode
is refused outright (``resolve_interpret`` raises): a run on the chip that
silently interpreted its kernels would measure the interpreter.
"""
from __future__ import annotations

import os
from typing import Optional

import jax

from repro.kernels.ttt_probe import (ProbeStepOut, SpecProbeOut,
                                     make_unroll_kernel, serving_probe_step,
                                     serving_probe_spec_step,
                                     ttt_probe_batched, ttt_probe_scan)
from repro.kernels.flash_attention import flash_attention
from repro.kernels.decode_attention import (flash_decode, paged_flash_decode,
                                             paged_flash_packed_chunk,
                                             paged_flash_prefill_chunk)
from repro.kernels.rwkv6_scan import wkv_scan


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def default_interpret() -> bool:
    forced = os.environ.get("REPRO_PALLAS_INTERPRET")
    if forced is not None and forced != "":
        return forced not in ("0", "false", "False")
    return not on_tpu()


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag a kernel call site uses: the explicit value,
    else ``default_interpret()``.  Raises on a TPU backend if that comes out
    True — the chip always runs the Mosaic-compiled kernels."""
    interp = default_interpret() if interpret is None else bool(interpret)
    if interp and on_tpu():
        raise RuntimeError(
            "Pallas interpret mode requested on a TPU backend (explicit "
            "interpret=True or REPRO_PALLAS_INTERPRET); unset it — the chip "
            "runs the compiled kernels")
    return interp


__all__ = ["ProbeStepOut", "SpecProbeOut", "ttt_probe_scan",
           "ttt_probe_batched", "make_unroll_kernel", "serving_probe_step",
           "serving_probe_spec_step", "flash_attention",
           "flash_decode", "paged_flash_decode", "paged_flash_packed_chunk",
           "paged_flash_prefill_chunk", "wkv_scan", "on_tpu",
           "default_interpret", "resolve_interpret"]
