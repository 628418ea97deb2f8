"""Traffic generation for the benchmark, from a seed and a mix file.

The counter-based splitmix64 draws (``crn_bits``, ``crn_u01``), the
exponential gaps of the Poisson process and ``nearest_rank`` are
copies of ``repro.serving.traffic`` and ``repro.serving.slo``: kept
here so that no change to the program can
move the yardstick.  Every draw is a pure function of
``(seed, stream, index)``, so the same seed gives the same traffic.

A mix file (``bench/traffic/<mix>.json``) holds only parameters:

* ``lo``: a closed loop.  ``clients`` requests are outstanding at all
  times (``"resident_slots"`` means the configuration's slot count).
* ``hi``: Poisson arrivals at ``rate_per_s``, or ``null``.  A window
  of ``seconds`` holds ``n = round(rate * seconds)`` of them, at the
  times of a Poisson process given that it has ``n`` arrivals in the
  window: ``n + 1`` exponential gaps drawn as ``repro.serving.traffic``
  draws them, scaled to fill the window.  Bursts and lulls of every
  length inside the window follow the Poisson law; only the window's
  total is fixed, so that every seed offers the same amount of HI work.

Each class takes its (prompt, output) lengths from a deck of ``deck``
entries, reshuffled by the seed every ``deck`` requests: every seed
sends the same sizes, in another order.  Lengths are bounded-Pareto
(``min``, ``max``, ``alpha``) quantiles, and prompts are rounded up to
the next of ``buckets``, so set-up compiles a fixed set of prompt
shapes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import math
from typing import List, Optional, Sequence, Tuple

import numpy as np

_GOLD = np.uint64(0x9E3779B97F4A7C15)
_U53 = 1.0 / (1 << 53)


def _mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64 finalizer (uint64 wrap-around is the arithmetic)."""
    x = np.asarray(x, np.uint64)
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def stream_key(stream) -> np.uint64:
    h = hashlib.sha256(repr(stream).encode()).digest()
    return np.uint64(int.from_bytes(h[:8], "little"))


def crn_bits(seed: int, stream, index, sub: int = 0) -> np.ndarray:
    """Raw 64-bit draw keyed ``(seed, stream, index, sub)``."""
    idx = np.asarray(index, np.uint64)
    with np.errstate(over="ignore"):
        base = _mix64(np.asarray(_mix64(np.uint64(seed) * _GOLD)
                                 ^ stream_key(stream)))
        ctr = (idx << np.uint64(8)) + np.uint64(sub)
        s = base + ctr * _GOLD
    return _mix64(s)


def crn_u01(seed: int, stream, index, sub: int = 0) -> np.ndarray:
    """Uniform [0, 1) doubles from the top 53 bits of :func:`crn_bits`."""
    return (crn_bits(seed, stream, index, sub) >> np.uint64(11)) \
        .astype(np.float64) * _U53


def nearest_rank(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank quantile: the ceil(q*n)-th smallest value."""
    if not 0.0 < q <= 1.0:
        raise ValueError(f"quantile q={q} must be in (0, 1]")
    xs = sorted(values)
    if not xs:
        return None
    return float(xs[max(0, math.ceil(q * len(xs)) - 1)])


def permutation(seed: int, stream, n: int, cycle: int = 0) -> np.ndarray:
    """A seed-keyed permutation of ``range(n)`` (argsort of CRN draws)."""
    return np.argsort(crn_u01(seed, (stream, cycle), np.arange(n)),
                      kind="stable")


def pareto_quantiles(lo: float, hi: float, alpha: float, n: int
                     ) -> np.ndarray:
    """The ``(k + 0.5) / n`` quantiles of a Pareto law on [lo, hi]."""
    u = (np.arange(n) + 0.5) / n
    return lo / (1.0 - u * (1.0 - (lo / hi) ** alpha)) ** (1.0 / alpha)


def lengths(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified lengths of one length spec, rounded up to its
    buckets where it has them (ints, ascending order)."""
    x = np.ceil(pareto_quantiles(spec["min"], spec["max"], spec["alpha"],
                                 n)).astype(np.int64)
    buckets = spec.get("buckets")
    if buckets:
        b = np.asarray(sorted(buckets), np.int64)
        x = b[np.minimum(np.searchsorted(b, x), len(b) - 1)]
    return x


def prompt_lens(mix: dict) -> List[int]:
    """Every prompt length the mix sends: the shapes set-up warms, and
    no others."""
    out = set()
    for part in (mix["lo"], mix.get("hi")):
        if part:
            out |= set(lengths(part["prompt"], int(part["deck"])).tolist())
    return sorted(out)


def max_request_len(mix: dict) -> int:
    """Longest prompt plus longest output the mix can send."""
    return max(max(part["prompt"]["buckets"]) + part["output"]["max"]
               for part in (mix["lo"], mix.get("hi")) if part)


@dataclasses.dataclass(frozen=True)
class Spec:
    """One request-to-be: due time (s from window start; ``None`` for
    a closed-loop LO request, due when its client sends it), shape."""
    crit: str                 # "HI" or "LO"
    index: int                # per-class sequence number
    t: Optional[float]
    prompt_len: int
    max_new_tokens: int


class Deck:
    """Request shapes of one class: request ``j`` is entry ``j % n`` of
    a deck of (prompt, output) pairs, the deck shuffled anew by the
    seed every ``n`` requests.  Prompts and outputs are paired in a
    seed-independent way (ascending prompt with a fixed interleave of
    outputs), so every cycle is the same set of requests."""

    def __init__(self, seed: int, part: dict, crit: str):
        self.seed, self.crit = seed, crit
        self.stream = f"{crit.lower()}_deck"
        self.n = int(part["deck"])
        prompts = lengths(part["prompt"], self.n)
        outputs = lengths(part["output"], self.n)
        # a fixed pairing: long outputs go with both short and long
        # prompts, not all with one end of the prompt range
        order = np.argsort(crn_u01(0, f"{crit.lower()}_pairing",
                                   np.arange(self.n)), kind="stable")
        self.pairs: List[Tuple[int, int]] = [
            (int(prompts[k]), int(outputs[order[k]]))
            for k in range(self.n)]

    def spec(self, j: int, t: Optional[float] = None) -> Spec:
        cycle, k = divmod(j, self.n)
        p, o = self.pairs[int(permutation(self.seed, self.stream, self.n,
                                          cycle)[k])]
        return Spec(self.crit, j, t, p, o)


def exponential_gaps(seed: int, stream, n: int) -> np.ndarray:
    """``n`` unit-mean exponential gaps by inverse CDF of CRN draws (as
    ``repro.serving.traffic.Poisson.inter_arrivals`` draws them)."""
    return -np.log1p(-crn_u01(seed, stream, np.arange(n)))


def poisson_times(seed: int, stream, n: int, seconds: float) -> np.ndarray:
    """The times of ``n`` arrivals of a Poisson process given that it
    has ``n`` in ``[0, seconds)``: the first ``n`` partial sums of
    ``n + 1`` exponential gaps, over their total, times ``seconds``
    (the ordered times of ``n`` uniform draws on the window)."""
    s = np.cumsum(exponential_gaps(seed, stream, n + 1))
    return seconds * s[:n] / s[n]


def hi_arrivals(seed: int, mix: dict, seconds: float) -> List[Spec]:
    """The window's HI arrivals: ``round(rate * seconds)`` Poisson
    arrivals, their shapes from the HI deck."""
    hi = mix.get("hi")
    if not hi:
        return []
    n = int(round(hi["rate_per_s"] * seconds))
    deck = Deck(seed, hi, "HI")
    return [deck.spec(i, float(t)) for i, t in
            enumerate(poisson_times(seed, "hi_arrivals", n, seconds))]


def prompt_tokens(seed: int, crit: str, index: int, n: int,
                  vocab: int) -> np.ndarray:
    """The prompt's token ids, uniform over the vocabulary."""
    bits = crn_bits(seed, ("prompt", crit, index), np.arange(n))
    return (bits % np.uint64(vocab)).astype(np.int32)


def key_words(seed: int, stream: str = "weights") -> np.ndarray:
    """Two 32-bit words for a JAX threefry key, from any whole seed."""
    b = int(crn_bits(seed, stream, 0))
    return np.asarray([b >> 32, b & 0xFFFFFFFF], np.uint32)
