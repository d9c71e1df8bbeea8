"""Batch law ``pack``: documents packed to the sequence, an EOS id every
``doc`` tokens, ``doc`` drawn once a batch from ``[doc_min, doc_max)``.

    {"law": "pack", "eos_id": 2, "doc_min": 256, "doc_max": 1024}
"""
import numpy as np


def apply(part: dict, rng: np.random.Generator, toks: np.ndarray):
    doc = int(rng.integers(part["doc_min"], part["doc_max"]))
    toks[:, ::doc] = part["eos_id"]
