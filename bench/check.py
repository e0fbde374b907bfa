"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests it served, drawn from the seed with the longest in it, is run
through the plain float32 reference of the configuration's family
(``bench.families``, run by ``bench.reference.run_rows``) from the same
weights, made again from the seed:

* ``logit_gap`` — over every served token, the widest gap by which the
  served token's reference logit lies below the reference's best logit.
  Served tokens are greedy, so a sound program serves the reference's best
  up to rounding.  This covers the paged decode kernel, the packed-chunk
  prefill that wrote the cache it reads (a cell's whole context was
  prefilled by it), and in the MoE configuration the routed top-k of its
  experts.  ``mean_gap`` is the same gap averaged over the served tokens,
  and ``flip_share`` the share of served tokens that are not the
  reference's best.
* ``score_gap`` — over every probe boundary, the widest difference between
  the program's smoothed probe score and the reference probe's, computed
  from the reference's hidden states (``bench.probe_ref``); ``score_mean``
  is its mean.  It covers the probe's pooling, score, smoothing and
  test-time update of the fast weights.
* ``stop_mismatch`` — requests whose stop at the calibrated lambda* (with
  the program's default burn-in) differs from the reference's where the
  reference is clear of lambda* by more than the score limit.

Which of these a cell holds to a limit is the set of keys of its file
``bench/limits/<workload>.json``; the others are printed beside them.

The engine feeds token 0 at the first position after the prompt and emits
the argmax there as the first served token (``ContinuousServingEngine.
finish_prefill`` / ``admit``), so the sequence the reference runs is
context + [0] + served[:-1], and served token k is compared at position
len(context) + k.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Optional

import numpy as np

from bench import probe_ref as PR
from bench import reference as R

READINGS = ("logit_gap", "mean_gap", "flip_share", "score_gap", "score_mean",
            "stop_mismatch")


@dataclasses.dataclass
class Probe:
    """What the reference probe needs: the calibrated slow weights the
    program serves with, its rate and window, and the stop rule."""
    w0: np.ndarray
    b0: float
    eta: float
    window: int
    tokens_per_step: int
    lam: float
    burn_in: int


def sample(served, n: int, seed: int, min_tokens: int):
    """``n`` requests with at least ``min_tokens`` served tokens: the one
    with the most served tokens, the one with the longest context, and the
    rest drawn from the seed."""
    pool = [s for s in served if len(s.tokens) >= min_tokens]
    if not pool:
        return []
    pick = [max(pool, key=lambda s: len(s.tokens)),
            max(pool, key=lambda s: len(s.context) + len(s.tokens))]
    rest = [s for s in pool if all(s is not p for p in pick)]
    rng = np.random.default_rng(seed + 1)
    order = rng.permutation(len(rest))
    out = []
    for s in pick + [rest[i] for i in order]:
        if all(s is not o for o in out):
            out.append(s)
    return out[:n]


def padded(n: int, block: int) -> int:
    return -(-n // block) * block


# what stands in the program's place: the program itself, the control (the
# family's reference one precision below, the probe in bfloat16), or the
# probe fault (the reference with the probe's fast weights left unchanged)
VARIANTS = ("program", "control", "probe_frozen")


def compare(family, cfg: dict, weights, picked, probe: Probe, t_pad: int,
            *, variant: str = "program", score_limit: float = 0.0
            ) -> Dict[str, float]:
    """Readings of ``variant``'s served tokens and probe scores against
    ``family``'s float32 reference and the float64 reference probe."""
    if variant not in VARIANTS:
        raise ValueError(f"unknown variant {variant!r}")
    gaps, score_diffs, ref_means = [], [], []
    mismatch = 0
    t0 = time.perf_counter()
    for s in picked:
        p, n = len(s.context), len(s.tokens)
        seq = np.zeros((t_pad,), np.int32)
        seq[:p] = s.context
        seq[p + 1:p + n] = s.tokens[:-1]          # seq[p] is the token 0
        h_ref, lg_ref = R.run_rows(family, cfg, weights, seq, p, n)
        lg_ref = lg_ref.astype(np.float64)
        best = lg_ref.max(axis=1)
        ref_scores = PR.probe_scores(h_ref, probe.w0, probe.b0,
                                     probe.eta, probe.tokens_per_step,
                                     probe.window)
        args = (probe.w0, probe.b0, probe.eta, probe.tokens_per_step,
                probe.window)
        if variant == "control":
            h_alt, lg_alt = R.run_rows(family, cfg, weights, seq, p, n,
                                      low=True)
            chosen = lg_alt.argmax(axis=1)
            got = PR.probe_scores(h_alt, *args, low=True)
        elif variant == "probe_frozen":
            chosen = lg_ref.argmax(axis=1)
            got = PR.probe_scores(h_ref, *args, update=False)
        else:
            chosen = np.asarray(s.tokens)
            got = np.asarray(s.scores, np.float64)
        gaps.append(best - lg_ref[np.arange(n), chosen])
        m = min(len(got), len(ref_scores))
        if m:
            score_diffs.append(np.abs(got[:m] - ref_scores[:m]))
            ref_means.append(ref_scores[:m])
            mismatch += int(PR.stop_disagrees(got[:m], ref_scores[:m],
                                              probe.lam, probe.burn_in,
                                              score_limit))
    g = np.concatenate(gaps) if gaps else np.zeros((0,))
    d = np.concatenate(score_diffs) if score_diffs else np.zeros((0,))
    ref = np.concatenate(ref_means) if ref_means else np.zeros((0,))
    return {"logit_gap": float(g.max(initial=0.0)),
            "mean_gap": float(g.mean()) if g.size else 0.0,
            "flip_share": float((g > 0).mean()) if g.size else 0.0,
            "score_gap": float(d.max(initial=0.0)),
            "score_mean": float(d.mean()) if d.size else 0.0,
            "stop_mismatch": float(mismatch), "requests": float(len(picked)),
            "tokens": float(g.size), "scores": float(d.size),
            "reference_score_mean": float(ref.mean()) if ref.size else 0.0,
            "reference_s": time.perf_counter() - t0}


def verdict(readings: Dict[str, float], limits: Dict[str, float]
            ) -> Optional[bool]:
    """True when every number the cell holds to a limit is within it."""
    if readings.get("requests", 0) == 0:
        return False
    return all(readings[k] <= v for k, v in limits.items())

