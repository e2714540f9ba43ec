"""The one traffic generator: reads a mix file of ``bench/traffic`` and
draws requests from ``--seed``.

Every seed gets the same multiset of lengths and only another order of it
and other token ids, so that two seeds do the same amount of work: a pool
of ``pool`` lengths is laid at the pool's quantiles of the stated
distribution, and the stream is a run of fresh permutations of that pool.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def seed_key(seed: int) -> np.ndarray:
    """A threefry key (uint32[2]) that holds all 64 bits of ``seed``."""
    if not 0 <= seed < 2 ** 64:
        raise ValueError(f"--seed must lie in [0, 2**64): {seed}")
    return np.array([seed >> 32, seed & 0xFFFFFFFF], np.uint32)


def rng(seed: int, stream: str) -> np.random.Generator:
    """An independent numpy stream per purpose, so that drawing more of one
    (a longer window) leaves the others as they were."""
    return np.random.default_rng([seed, *stream.encode()])


def quantile_pool(spec: dict, n: int) -> np.ndarray:
    """``n`` integer lengths at the quantiles (i + 0.5) / n of ``spec``."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "uniform":
        x = spec["lo"] + q * (spec["hi"] + 1 - spec["lo"])
    elif spec["dist"] == "lognormal":
        z = np.array([NormalDist().inv_cdf(v) for v in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    return np.clip(np.floor(x), spec["lo"], spec["hi"]).astype(np.int64)


class Stream:
    """Endless lengths: successive seeded permutations of one pool."""

    def __init__(self, spec: dict, pool: int, r: np.random.Generator):
        self.pool = quantile_pool(spec, pool)
        self.r = r
        self.buf: list[int] = []

    def __next__(self) -> int:
        if not self.buf:
            self.buf = list(self.r.permutation(self.pool))
        return int(self.buf.pop())

    def take(self, n: int) -> list[int]:
        return [next(self) for _ in range(n)]


def tokens(r: np.random.Generator, n: int, vocab: int) -> np.ndarray:
    return r.integers(0, vocab, n, dtype=np.int32)


def bucket_of(n: int, buckets) -> int:
    """Index of the smallest bucket length that holds ``n`` tokens."""
    for i, (length, _) in enumerate(buckets):
        if n <= length:
            return i
    raise ValueError(f"a prompt of {n} tokens exceeds every bucket")


def pad_to(n: int, multiple: int) -> int:
    return int(math.ceil(n / multiple) * multiple)
