"""Traffic generation from a workload file's parameters and a seed.

The benchmark's own generators, so that a later change to the program
cannot move the yardstick. Every form is stratified: each seed draws the
same multiset of gaps, lengths and budgets in another order, so seeds
change the order of the work and not its amount.
"""
from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np


def seed32(seed: int, *salt: int) -> int:
    """A 31-bit seed for JAX keys, derived from any whole-number seed."""
    ss = np.random.SeedSequence([seed % (1 << 64), *salt])
    return int(ss.generate_state(1)[0] & 0x7FFFFFFF)


def rng_for(seed: int, *salt: int) -> np.random.Generator:
    return np.random.default_rng([seed % (1 << 64), *salt])


def _midpoints(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def stratified_poisson_gaps(rate_hz: float, n: int,
                            rng: np.random.Generator) -> np.ndarray:
    """n exponential interarrival gaps (seconds) at the n midpoint
    quantiles, shuffled: every seed offers the same total load."""
    gaps = -np.log1p(-_midpoints(n)) / rate_hz
    return rng.permutation(gaps)


def stratified_lognormal_ints(n: int, median: float, sigma: float, lo: int,
                              hi: int, rng: np.random.Generator) -> np.ndarray:
    """n integers from a lognormal at midpoint quantiles, clipped to
    [lo, hi], shuffled."""
    z = np.asarray([NormalDist().inv_cdf(u) for u in _midpoints(n)])
    vals = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi)
    return rng.permutation(vals.astype(np.int64))


def stratified_uniform(n: int, lo: float, hi: float,
                       rng: np.random.Generator) -> np.ndarray:
    """n values uniform on [lo, hi] at midpoint quantiles, shuffled."""
    return rng.permutation(lo + (hi - lo) * _midpoints(n)).astype(np.float32)


def budgets(n: int, spec: dict, rng: np.random.Generator) -> np.ndarray:
    """Per-request budgets: `below_share` of them at `below_value`
    (under every cost, so the cheapest-model fallback serves them), the
    rest stratified-uniform on `uniform`."""
    n_below = int(math.floor(spec.get("below_share", 0.0) * n + 0.5))
    lo, hi = spec["uniform"]
    rest = stratified_uniform(n - n_below, lo, hi, rng)
    out = np.concatenate([np.full(n_below, spec.get("below_value", 0.0),
                                  np.float32), rest])
    return rng.permutation(out).astype(np.float32)
