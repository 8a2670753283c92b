"""Seeded traffic: lengths and prompts for every traffic mix.

Copied from the program's load generator (``repro.traffic.loadgen``:
``make_prompts``) so that the yardstick does not move with the program,
and extended with log-normal lengths.

The values are the quantiles of the stated distribution at the
midpoints of n equal strata, in an order drawn from a generator. A
driver draws its sizes, their order and its arrivals from
``schedule_rng``, which is the same for every seed, and its token ids,
frames and weights from the seed: two seeds then give the window the
same work, so the spread of a cell's runs is the system's own.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy generator per (seed, stream)."""
    return np.random.default_rng([int(seed) & (2 ** 64 - 1), stream])


def schedule_rng(stream: int) -> np.random.Generator:
    """The generator of sizes, orders and arrivals: one for all seeds."""
    return rng(0, stream)


def strata(n: int) -> np.ndarray:
    """Midpoints of n equal probability strata: (i + 0.5) / n."""
    return (np.arange(n) + 0.5) / n


def lognormal(n: int, spec: dict, g: np.random.Generator) -> np.ndarray:
    """n integer lengths from a log-normal of median ``spec["median"]``
    and log-space sigma ``spec["sigma"]``, clipped to [lo, hi], in an
    order drawn from ``g``."""
    z = np.array([NormalDist().inv_cdf(p) for p in strata(n)])
    vals = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    vals = np.clip(np.rint(vals), spec["lo"], spec["hi"]).astype(np.int64)
    return g.permutation(vals)


def fractions(n: int, g: np.random.Generator) -> np.ndarray:
    """n stratified fractions in (0, 1), shuffled: how much of its budget
    each request of a first wave has left."""
    return g.permutation(strata(n))


def prompts(lengths, vocab: int, g: np.random.Generator) -> list:
    """One int32 prompt of each length, token ids uniform in [0, vocab)."""
    return [g.integers(0, vocab, size=int(n)).astype(np.int32)
            for n in lengths]


def bucket(n: int, cap: int) -> int:
    """Next power of two >= n, capped at ``cap`` (the scheduler's prefill
    bucket rule)."""
    b = 1
    while b < n:
        b <<= 1
    return min(b, cap)
