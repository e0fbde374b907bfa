"""One general generator for every traffic mix.

A mix is a data file under ``bench/traffic/``.  Its lengths are drawn by
stratified quantiles: ``quantiles`` levels of each length distribution,
``(i + 0.5) / quantiles`` for i = 0..quantiles-1, paired by a fixed rule.
Each block of ``quantiles`` requests is the whole quantile set, in an
order drawn from one fixed generator, so every seed hands out the same
sizes in the same order and only draws the tokens: a window holds one or
two blocks, and which sizes fall inside it would otherwise change the work
from seed to seed.

Every client's first request is a continuation: its context is the prompt
plus the tokens served so far, at a progress through its served length, so
a window starts in a steady state over long caches.  The probe's stop is
held off (``burn_in_past``): the traffic, not the probe, sets each served
length.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Optional

import numpy as np

# fixed pairing multipliers: level i of the prompt lengths goes with level
# (A * i) % K of the served lengths and (B * i + 1) % K of the progress, so
# long prompts are not always paired with long answers (coprime to any
# power of two)
_PAIR_SERVED, _PAIR_PROGRESS = 5, 3


@dataclasses.dataclass
class Item:
    """One request as the generator makes it."""
    client: int
    index: int               # position in the stream of its client
    tokens: np.ndarray       # (prompt_len,) int32: what is prefilled
    served: int              # output tokens it is to be served
    prompt: int              # prompt length before any continuation


def quantile_levels(spec: dict, k: int) -> List[int]:
    """The k stratified quantiles of one length distribution."""
    lo, hi = float(spec["lo"]), float(spec["hi"])
    out = []
    for i in range(k):
        u = (i + 0.5) / k
        if spec["dist"] == "loguniform":
            v = lo * (hi / lo) ** u
        elif spec["dist"] == "uniform":
            v = lo + (hi - lo) * u
        else:
            raise ValueError(f"unknown length distribution {spec['dist']!r}")
        out.append(int(round(v)))
    return out


def _u32(seed: int) -> np.random.Generator:
    if seed < 0:
        raise ValueError(f"--seed must be >= 0, got {seed}")
    return np.random.default_rng(seed)


class Traffic:
    """A closed loop of ``clients`` clients over one stream of requests.

    Client c's first request is ``first(c)``; whenever one of its requests
    completes, the client takes ``next()``: the next request of one stream
    shared by all clients, made of blocks of ``quantiles`` requests, each
    block the whole quantile set in the fixed order."""

    def __init__(self, mix: dict, vocab: int, seed: int):
        self.mix, self.vocab = mix, int(vocab)
        self.k = int(mix["quantiles"])
        self.clients = int(mix["clients"])
        self.rng = _u32(seed)
        k = self.k
        prompts = quantile_levels(mix["prompt_tokens"], k)
        served = quantile_levels(mix["served_tokens"], k)
        self.levels = [(prompts[i], served[(_PAIR_SERVED * i) % k],
                        (((_PAIR_PROGRESS * i + 1) % k) + 0.5) / k)
                       for i in range(k)]
        self._block: List[int] = []
        self._orders = np.random.default_rng(0)
        self._firsts = self._take(self.clients)

    def _take(self, n: int) -> List[int]:
        out = []
        for _ in range(n):
            if not self._block:
                self._block = [int(i) for i in self._orders.permutation(self.k)]
            out.append(self._block.pop(0))
        return out

    def _tokens(self, n: int) -> np.ndarray:
        return self.rng.integers(0, self.vocab, size=(n,), dtype=np.int32)

    def first(self, client: int) -> Item:
        """Client ``client``'s first request: a continuation whose context
        is the prompt plus the tokens served so far, at the level's
        progress through its served length; only the rest is still to be
        served."""
        prompt, served, progress = self.levels[self._firsts[client]]
        done = int(progress * served)
        ctx = self._tokens(prompt + done)
        return Item(client, 0, ctx, max(served - done, 1), prompt)

    def next(self, client: int, index: int) -> Item:
        lvl = self._take(1)[0]
        prompt, served, _ = self.levels[lvl]
        return Item(client, index, self._tokens(prompt), served, prompt)

    def max_context(self) -> int:
        """The longest context any request of the mix can reach."""
        return max(p + s for p, s, _ in self.levels)


def burn_in_past(mix: dict, tokens_per_step: int,
                 default: int) -> Optional[int]:
    """A probe burn-in past the longest served length, so the probe runs at
    every boundary but never stops a request: the traffic, not the probe,
    sets each served length.  None keeps the program's default when that
    already lies past it."""
    longest = max(quantile_levels(mix["served_tokens"], int(mix["quantiles"])))
    need = math.ceil(longest / tokens_per_step) + 1
    return need if need > default else None
