"""Stratified sampling: a mix's lengths and gaps are the evenly spaced
quantiles of its distributions, so every seed offers the same multiset
(the same tokens, the same mean rate) and decides only the order and
the pairing."""
from __future__ import annotations

import math

import numpy as np


def quantile_grid(n):
    """n mid-point quantiles in (0, 1)."""
    return (np.arange(n, dtype=np.float64) + 0.5) / n


def log_uniform_lengths(lo, hi, n):
    """n whole lengths at the quantiles of a log-uniform law on
    [lo, hi], ascending."""
    q = quantile_grid(n)
    vals = np.exp(math.log(lo) + q * (math.log(hi) - math.log(lo)))
    return np.clip(np.rint(vals), lo, hi).astype(np.int64)


def exponential_gaps(rate, n):
    """n gaps at the quantiles of an exponential law, rescaled so that
    their mean is exactly 1/rate, ascending."""
    gaps = -np.log1p(-quantile_grid(n))
    return gaps * (n / rate / gaps.sum())


def shuffled(values, rng):
    out = np.array(values, copy=True)
    rng.shuffle(out)
    return out


def rng_of(seed, stream):
    """An independent generator for one use (`stream` is a small whole
    number naming it) of one --seed, which may pass 2**31."""
    return np.random.default_rng([int(seed), int(stream)])
