"""The one request generator: reads a traffic mix file and yields chunks.

A mix (``bench/traffic/<mix>.json``) gives length distributions, an arrival
process, the chunk size and optionally ``"order": "longest_first"`` and
``"warm_chunks"`` (chunks served in set-up).  Every chunk holds the same
multiset of prompt lengths, output lengths and arrival gaps -- stratified
quantiles of the mix's distributions -- in an order, and with token ids,
drawn from the run's seed.  So runs with different seeds do the same amount
of work.
"""
from __future__ import annotations

import dataclasses
from statistics import NormalDist

import numpy as np
from scipy.special import gammaincinv


@dataclasses.dataclass
class Item:
    """One request as the generator makes it (engine-agnostic)."""
    arrival_step: int
    prompt: np.ndarray      # int32 token ids
    max_new: int
    seed: int               # the request's sampling seed (int32 range)


def quantiles(spec: dict, n: int) -> np.ndarray:
    """``n`` stratified quantiles (at (i + 1/2) / n) of a length or gap
    distribution, unclipped and unrounded."""
    p = (np.arange(n) + 0.5) / n
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(x)) for x in p])
        return spec["median"] * np.exp(spec["sigma"] * z)
    if kind == "uniform":
        return spec["min"] + p * (spec["max"] - spec["min"])
    if kind == "gamma":                     # mean 1/rate, shape k
        k = spec["shape"]
        return gammaincinv(k, p) / (k * spec["rate"])
    raise ValueError(f"unknown distribution {kind!r}")


def lengths(spec: dict, n: int, scale: float, avoid=()) -> np.ndarray:
    """Integer lengths in [min, max] (both scaled by ``scale``).  A length
    equal to one of ``avoid`` (the prefill buckets: a prompt that fills a
    bucket exactly is served by a program of its own) moves down by one."""
    lo = max(1, int(round(spec["min"] * scale)))
    hi = max(lo, int(round(spec["max"] * scale)))
    v = np.clip(np.round(quantiles(spec, n) * scale), lo, hi).astype(np.int64)
    for b in avoid:
        v[v == b] = b - 1
    return v


def scaled_buckets(mix: dict, scale: float) -> tuple:
    return tuple(sorted({max(2, int(round(b * scale)))
                         for b in mix["prefill_buckets"]}))


class Stream:
    """The seeded request stream of one mix: ``next_chunk()`` returns the
    next chunk's requests, arrival steps relative to the chunk's start."""

    def __init__(self, mix: dict, vocab: int, seed: int, *, cache_len: int,
                 scale=1.0, rate=None, n=None):
        """``scale`` shrinks the prompts and buckets (a rehearsal's small
        cache); outputs keep their lengths, cut where a prompt and its
        output would outgrow ``cache_len``."""
        self.mix = mix
        self.vocab = vocab
        self.rng = np.random.default_rng(seed % (1 << 64))
        n = n or mix["chunk_requests"]
        self.buckets = scaled_buckets(mix, scale)
        self.prompt_lens = lengths(mix["prompt"], n, scale, self.buckets)
        self.output_lens = np.minimum(lengths(mix["output"], n, 1.0),
                                      cache_len - self.prompt_lens.max())
        arr = dict(mix["arrivals"])
        if rate is not None:
            arr["rate"] = rate
        self.gaps = (quantiles(arr, n) if arr["dist"] != "backlog"
                     else np.zeros(n))

    def next_chunk(self) -> list:
        rng = self.rng
        n = len(self.prompt_lens)
        plen = rng.permutation(self.prompt_lens)
        olen = rng.permutation(self.output_lens)
        if self.mix.get("order") == "longest_first":
            # A backlog served longest output first keeps the slots full
            # to the chunk's end, as a server whose queue never empties.
            olen = np.sort(olen)[::-1]
        steps = np.floor(np.cumsum(rng.permutation(self.gaps))).astype(int)
        seeds = rng.integers(0, 2**31 - 1, size=n)
        return [Item(int(steps[i]),
                     rng.integers(0, self.vocab, size=int(plen[i]),
                                  dtype=np.int32),
                     int(olen[i]), int(seeds[i]))
                for i in range(n)]
