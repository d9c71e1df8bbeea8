"""Size law ``lognormal``: ``n`` sizes at the midpoint quantiles of a
lognormal with the given median and sigma, rounded and clipped, so every
seed gets the same multiset.

    {"law": "lognormal", "median": 128, "sigma": 0.6, "min": 32, "max": 512}
"""
import math
from statistics import NormalDist

import numpy as np


def sizes(part: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)])
    x = np.exp(math.log(part["median"]) + part["sigma"] * z)
    return np.clip(np.round(x), part["min"], part["max"]).astype(int)
