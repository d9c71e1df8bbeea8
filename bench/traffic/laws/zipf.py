"""Token law ``zipf``: ids over ``[first_id, vocab)`` where rank ``r`` has
weight ``1/r`` (after ``data/pipeline._host_tokens``).

    {"law": "zipf", "first_id": 3}
"""
import numpy as np


def ids(part: dict, rng: np.random.Generator, shape, vocab: int):
    first = part["first_id"]
    cdf = np.cumsum(1.0 / np.arange(1, vocab - first + 1))
    ranks = np.searchsorted(cdf, rng.random(shape) * cdf[-1])
    return (first + np.minimum(ranks, vocab - first - 1)).astype(np.int32)
