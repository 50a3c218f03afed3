"""Seeded traffic from a mix's data file (``bench/traffic/<name>.json``).

Lengths are the quantiles ``(i + 0.5) / n`` of the stated distribution,
clipped to its range, and their order, like that of an open loop's
gaps, is drawn once from ``SCHEDULE_SEED``: every seed replays the same
schedule, and draws its own token ids (and weights). A tail over a
hundred requests depends on which long prompts meet which bursts, so a
fresh order per seed moves it by a tenth; a replay of one schedule
measures the system and not the draw.

Open loop (``"loop": "open"``): ``round(rate * seconds)`` requests, with
exponential gaps at ``rate_per_s`` (the first from the window's start)
rescaled so that the last is due half a mean gap before the window
closes. Closed loop (``"loop": "closed"``): ``clients`` clients, each
sending its next request when its last one ends; they take requests in
order from rounds of ``clients`` specs, each round the whole quantile
set with token ids of its own, so no request is sent twice.
"""
from __future__ import annotations

import dataclasses
import itertools
import math
from statistics import NormalDist
from typing import Iterator, List

import numpy as np

#: the seed of the order of lengths and gaps, the same for every run
SCHEDULE_SEED = 0


@dataclasses.dataclass(frozen=True)
class RequestSpec:
    due_s: float          # offset from the window's start (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new_tokens: int


def quantile_lengths(dist: dict, n: int) -> np.ndarray:
    """``n`` lengths at the quantiles ``(i + 0.5) / n`` of ``dist``."""
    lo, hi = int(dist["min"]), int(dist["max"])
    u = (np.arange(n) + 0.5) / n
    kind = dist["dist"]
    if kind == "lognormal":
        nd = NormalDist()
        z = np.array([nd.inv_cdf(float(p)) for p in u])
        vals = np.round(float(dist["median"])
                        * np.exp(float(dist["sigma"]) * z))
    elif kind == "uniform":
        vals = np.floor(lo + u * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(vals, lo, hi).astype(np.int64)


def _rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed) & 0xFFFFFFFF, int(seed) >> 32,
                                  stream])


def _specs(mix: dict, seed: int, n: int, vocab: int,
           rnd: int = 0) -> List[tuple]:
    """``n`` (prompt ids, answer length) pairs: the lengths in the fixed
    order, the ids drawn from ``seed``."""
    prompts = quantile_lengths(mix["prompt"], n)
    outputs = quantile_lengths(mix["output"], n)
    prompts = prompts[_rng(SCHEDULE_SEED, 10 * rnd + 1).permutation(n)]
    outputs = outputs[_rng(SCHEDULE_SEED, 10 * rnd + 2).permutation(n)]
    ids = _rng(seed, 10 * rnd + 3)
    return [(ids.integers(1, vocab, int(p), dtype=np.int32), int(o))
            for p, o in zip(prompts, outputs)]


def open_loop(mix: dict, seed: int, seconds: float, vocab: int
              ) -> List[RequestSpec]:
    """The window's requests, sorted by due time (all due in
    ``[0, seconds)``)."""
    rate = float(mix["rate_per_s"])
    n = max(1, int(round(rate * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = -np.log1p(-u) / rate
    gaps = gaps[_rng(SCHEDULE_SEED, 4).permutation(n)]
    gaps *= seconds * (1 - 0.5 / n) / gaps.sum()
    due = np.cumsum(gaps)
    return [RequestSpec(float(d), p, o)
            for d, (p, o) in zip(due, _specs(mix, seed, n, vocab))]


def closed_loop_specs(mix: dict, seed: int, vocab: int
                      ) -> Iterator[RequestSpec]:
    """The specs the clients take, in order and without end (due times
    are set when a client sends)."""
    n = int(mix["clients"])
    for rnd in itertools.count():
        for p, o in _specs(mix, seed, n, vocab, rnd):
            yield RequestSpec(math.nan, p, o)


def length_ranges(mix: dict):
    """(prompt min, prompt max, output min, output max) of a mix."""
    return (int(mix["prompt"]["min"]), int(mix["prompt"]["max"]),
            int(mix["output"]["min"]), int(mix["output"]["max"]))
