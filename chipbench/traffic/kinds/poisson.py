"""Poisson arrivals at a fixed rate, with the same gaps for every seed:
the exponential distribution's quantiles, seed-shuffled, laid end to end."""
import numpy as np


def due(spec: dict, rate: float, start: float, end: float,
        rng: np.random.Generator) -> np.ndarray:
    n = max(1, int(round(rate * (end - start))))
    q = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-q) / rate)
    t = start + np.cumsum(gaps) - gaps[0]
    return t[t < end]
