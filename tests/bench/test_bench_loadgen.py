"""The benchmark's traffic is a pure function of the seed and the mix:
every seed offers the same amount of work, HI arrivals follow the
Poisson law inside the window, and the sizes are the same set in
another order."""
import collections

import numpy as np
import pytest

from bench import loadgen, spec

MIXED = spec.cell("olmo-1b.hi_over_lo_chat").mix
DOC = spec.cell("phi4-mini-3.8b.hi_over_lo_longdoc").mix
BIG = 2 ** 31 + 12345


def test_crn_copy_matches_the_program():
    from repro.serving import slo, traffic
    idx = np.arange(100)
    for seed in (0, 7, BIG):
        assert np.array_equal(loadgen.crn_bits(seed, "s", idx),
                              traffic.crn_bits(seed, "s", idx))
        assert np.array_equal(loadgen.crn_u01(seed, "s", idx, 3),
                              traffic.crn_u01(seed, "s", idx, 3))
        assert np.array_equal(
            loadgen.exponential_gaps(seed, "g", 50) / 4.0,
            traffic.Poisson(4.0).inter_arrivals(seed, "g", 50))
    vals = list(np.linspace(0, 1, 37))
    for q in (0.5, 0.95, 0.99):
        assert loadgen.nearest_rank(vals, q) == slo.nearest_rank(vals, q)


@pytest.mark.parametrize("mix", [MIXED, DOC], ids=["chat", "longdoc"])
def test_same_seed_same_traffic(mix):
    a = loadgen.hi_arrivals(BIG, mix, 45)
    b = loadgen.hi_arrivals(BIG, mix, 45)
    assert a == b
    da, db = (loadgen.Deck(BIG, mix["lo"], "LO") for _ in range(2))
    assert [da.spec(j) for j in range(40)] == [db.spec(j) for j in range(40)]
    assert np.array_equal(loadgen.prompt_tokens(BIG, "LO", 3, 512, 50304),
                          loadgen.prompt_tokens(BIG, "LO", 3, 512, 50304))


@pytest.mark.parametrize("mix", [MIXED, DOC], ids=["chat", "longdoc"])
def test_seeds_differ_in_order_only(mix):
    a = loadgen.hi_arrivals(1, mix, 45)
    b = loadgen.hi_arrivals(2, mix, 45)
    assert len(a) == len(b) == round(mix["hi"]["rate_per_s"] * 45)
    assert [s.t for s in a] != [s.t for s in b]
    for arr in (a, b):
        t = [s.t for s in arr]
        assert t == sorted(t) and all(0 < x < 45 for x in t)
    for part, crit in ((mix["hi"], "HI"), (mix["lo"], "LO")):
        n = part["deck"]
        da, db = loadgen.Deck(1, part, crit), loadgen.Deck(2, part, crit)
        for cycle in range(3):
            ca = [da.spec(j) for j in range(cycle * n, (cycle + 1) * n)]
            cb = [db.spec(j) for j in range(cycle * n, (cycle + 1) * n)]
            assert ca != cb
            key = lambda s: (s.prompt_len, s.max_new_tokens)  # noqa: E731
            assert collections.Counter(map(key, ca)) == \
                collections.Counter(map(key, cb))


def test_hi_arrivals_follow_the_poisson_law():
    """Given its count, a Poisson process puts its arrivals uniformly on
    the window: gaps are exponential, and the count in any part of the
    window drifts binomially from seed to seed (no smoothing)."""
    mix = dict(MIXED, hi=dict(MIXED["hi"], rate_per_s=8.0))
    T, n = 45.0, 360
    times = np.asarray([[s.t for s in loadgen.hi_arrivals(seed, mix, T)]
                        for seed in range(BIG, BIG + 400)])
    gaps = np.diff(times, axis=1).ravel()
    assert gaps.mean() == pytest.approx(T / (n + 1), rel=0.01)
    assert gaps.std() / gaps.mean() == pytest.approx(1.0, abs=0.02)
    assert np.mean(gaps > 3 * T / (n + 1)) == pytest.approx(np.exp(-3),
                                                            rel=0.1)
    for part in (2.5, 10.0):                 # seconds at the window's start
        c = (times < part).sum(axis=1)
        p = part / T
        assert c.mean() == pytest.approx(n * p, rel=0.05)
        assert c.var() == pytest.approx(n * p * (1 - p), rel=0.2)


def test_lengths_are_heavy_tailed_bucketed_and_fit():
    lo = MIXED["lo"]
    p = loadgen.lengths(lo["prompt"], lo["deck"])
    assert set(p) <= set(lo["prompt"]["buckets"])
    assert np.median(p) < p.mean() < p.max()
    hi = MIXED["hi"]
    assert loadgen.prompt_lens(MIXED) == sorted(
        set(p) | set(loadgen.lengths(hi["prompt"], hi["deck"])))
    for name in ("olmo-1b.hi_over_lo_chat",
                 "phi4-mini-3.8b.hi_over_lo_longdoc"):
        cell = spec.cell(name)
        assert loadgen.max_request_len(cell.mix) \
            <= cell.config["serving"]["max_len"]
        sent = {s.prompt_len for s in loadgen.hi_arrivals(BIG, cell.mix, 51)}
        assert sent <= set(loadgen.prompt_lens(cell.mix))


def test_weight_key_distinguishes_large_seeds():
    keys = {tuple(loadgen.key_words(s)) for s in (0, 2 ** 32, 2 ** 33, BIG)}
    assert len(keys) == 4
