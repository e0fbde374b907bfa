"""The traffic generator: seeded, stratified, within the mix's ranges."""
import json
from collections import Counter
from pathlib import Path

import numpy as np
import pytest

from bench import traffic as T

MIXES = Path(__file__).resolve().parents[1] / "traffic"
ALL = sorted(p.stem for p in MIXES.glob("*.json"))
BIG_SEED = 2 ** 40 + 12345


def _mix(name):
    return json.loads((MIXES / f"{name}.json").read_text())


def _stream(mix, seed, n):
    t = T.Traffic(mix, 49152, seed)
    firsts = [t.first(c) for c in range(t.clients)]
    rest = [t.next(i % t.clients, 1) for i in range(n)]
    return firsts, rest


@pytest.mark.parametrize("name", ALL)
def test_same_seed_same_requests(name):
    a = _stream(_mix(name), BIG_SEED, 20)
    b = _stream(_mix(name), BIG_SEED, 20)
    for x, y in zip(a[0] + a[1], b[0] + b[1]):
        assert np.array_equal(x.tokens, y.tokens) and x.served == y.served


@pytest.mark.parametrize("name", ALL)
def test_every_block_holds_the_quantile_set(name):
    mix = _mix(name)
    k = mix["quantiles"]
    want = Counter((p, s) for p, s, _ in T.Traffic(mix, 10, 0).levels)
    for seed in (0, 7, BIG_SEED):
        t = T.Traffic(mix, 10, seed)
        firsts = [t.first(c) for c in range(t.clients)]
        # the stream continues the first block, then whole blocks follow
        rest = [t.next(0, 1) for _ in range(3 * k - len(firsts) % k)]
        fresh = [(f.prompt, None) for f in firsts] + \
            [(r.prompt, r.served) for r in rest]
        for b in range(0, len(fresh) - k + 1, k):
            block = fresh[b:b + k]
            assert Counter(p for p, _ in block) == Counter(
                p for p, _ in want)


@pytest.mark.parametrize("name", ALL)
def test_lengths_within_the_stated_ranges(name):
    mix = _mix(name)
    lo_p, hi_p = mix["prompt_tokens"]["lo"], mix["prompt_tokens"]["hi"]
    lo_s, hi_s = mix["served_tokens"]["lo"], mix["served_tokens"]["hi"]
    for p, s, prog in T.Traffic(mix, 10, 3).levels:
        assert lo_p <= p <= hi_p and lo_s <= s <= hi_s and 0 < prog < 1
    firsts, rest = _stream(mix, 5, 40)
    for it in firsts + rest:
        assert it.tokens.dtype == np.int32
        assert 0 <= it.tokens.min() and it.tokens.max() < 49152
        assert len(it.tokens) + it.served <= T.Traffic(mix, 10, 5)\
            .max_context()


def test_continuations_start_part_way():
    mix = _mix("reasoning")
    t = T.Traffic(mix, 100, 1)
    for c in range(t.clients):
        it = t.first(c)
        assert len(it.tokens) >= it.prompt
        assert it.served >= 1


def _sizes(mix, seed, n):
    t = T.Traffic(mix, 100, seed)
    items = [t.first(c) for c in range(t.clients)] + \
        [t.next(0, 1) for _ in range(n)]
    return [(it.prompt, len(it.tokens), it.served) for it in items], \
        [it.tokens for it in items]


def test_next_requests_are_whole_prompts_of_a_level():
    mix = _mix("reasoning")
    t = T.Traffic(mix, 100, 1)
    levels = {(p, s) for p, s, _ in t.levels}
    for _ in range(2 * t.k):
        it = t.next(0, 1)
        assert len(it.tokens) == it.prompt
        assert (it.prompt, it.served) in levels


def test_quantile_levels():
    assert T.quantile_levels({"dist": "uniform", "lo": 0, "hi": 8}, 4) == \
        [1, 3, 5, 7]
    lv = T.quantile_levels({"dist": "loguniform", "lo": 100, "hi": 10000},
                           2)
    assert lv == [316, 3162]
    with pytest.raises(ValueError):
        T.quantile_levels({"dist": "normal", "lo": 0, "hi": 1}, 2)


def test_held_stops_lie_past_the_longest_output():
    mix = _mix("reasoning")
    b = T.burn_in_past(mix, 16, 10)
    longest = max(s for _, s, _ in T.Traffic(mix, 10, 0).levels)
    assert b is not None and b * 16 > longest
    short = dict(mix, served_tokens={"dist": "uniform", "lo": 16, "hi": 64})
    assert T.burn_in_past(short, 16, 10) is None


@pytest.mark.parametrize("name", ALL)
def test_every_seed_serves_the_same_sizes(name):
    mix = _mix(name)
    (a, ta), (b, tb) = _sizes(mix, 1, 40), _sizes(mix, BIG_SEED, 40)
    assert a == b
    assert any(not np.array_equal(x, y) for x, y in zip(ta, tb))


def test_unknown_length_distribution_is_refused():
    mix = dict(_mix("reasoning"),
               served_tokens={"dist": "normal", "lo": 1, "hi": 2})
    with pytest.raises(ValueError):
        T.Traffic(mix, 100, 1)
