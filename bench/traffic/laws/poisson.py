"""Arrival law ``poisson``: ``rate x seconds`` exponential gaps at their
midpoint quantiles, scaled to fill the window, so every seed gets the same
multiset of gaps.

    {"law": "poisson", "rate_per_s": 4.0}
"""
import numpy as np


def gaps(part: dict, seconds: float) -> np.ndarray:
    rate = float(part["rate_per_s"])
    n = max(int(round(rate * seconds)), 1)
    g = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    return g * (seconds / g.sum())
