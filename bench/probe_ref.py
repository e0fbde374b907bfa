"""Plain reference of the served ORCA probe (Algorithm 2, no-QK variant).

Per request, from the decode hidden states of its served tokens: every
``tokens_per_step`` tokens the step embedding phi is the mean of their
hidden states; the score is s = sigmoid(W . phi + b) with the request's
own fast weights, the smoothed score is the mean of the last
``smooth_window`` scores, and then one Brier-gradient step towards the
label 0 updates (W, b) with rate ``eta``.  Fast weights start at the
calibrated (W0, b0) when the request is admitted.

``low=True`` is the probe's control: every quantity the probe holds
(hidden states, phi, W, b, the score, the ring and the smoothed score)
rounded to bfloat16, the step below the program's float32 probe state.
``update=False`` is a fault: the fast weights are left as they were
admitted, the test-time update skipped.
"""
from __future__ import annotations

from typing import List, Optional

import ml_dtypes
import numpy as np


def _bf16(x):
    return np.asarray(x, np.float64).astype(ml_dtypes.bfloat16).astype(
        np.float64)


def probe_scores(hidden: np.ndarray, w0: np.ndarray, b0: float, eta: float,
                 tokens_per_step: int, window: int, *, low: bool = False,
                 update: bool = True) -> np.ndarray:
    """Smoothed scores at each boundary.  hidden (n, d), float64 math (or
    bfloat16 storage with ``low``)."""
    r = _bf16 if low else (lambda x: np.asarray(x, np.float64))
    h = r(hidden)
    w, b = r(w0), r(b0)
    ring: List[float] = []
    out = []
    for m in range(h.shape[0] // tokens_per_step):
        phi = r(h[m * tokens_per_step:(m + 1) * tokens_per_step].mean(0))
        s = r(1.0 / (1.0 + np.exp(-r(phi @ w + b))))
        ring = (ring + [s])[-window:]
        out.append(r(sum(ring) / len(ring)))
        if update:
            coeff = r(2.0 * s * s * (1.0 - s))
            w = r(w - r(eta * coeff * phi))
            b = r(b - eta * coeff)
    return np.asarray(out, np.float64)


def stop_index(smoothed: np.ndarray, lam: float, burn_in: int
               ) -> Optional[int]:
    """The boundary (1-based count of scores) at which the calibrated test
    would stop the request, or None."""
    for m, s in enumerate(smoothed, start=1):
        if m > burn_in and s >= lam:
            return m
    return None


def stop_disagrees(prog: np.ndarray, ref: np.ndarray, lam: float,
                   burn_in: int, margin: float) -> bool:
    """Whether the stop the program's scores give differs from the
    reference's where the reference is clear of the threshold: a boundary
    whose reference score lies within ``margin`` of lambda (the score limit)
    may go either way, and the comparison only holds up to it."""
    a, b = stop_index(prog, lam, burn_in), stop_index(ref, lam, burn_in)
    if a == b:
        return False
    last = min(x for x in (a, b, len(ref)) if x is not None)
    near = np.abs(np.asarray(ref[:last]) - lam) < margin
    return not near[burn_in:].any()
