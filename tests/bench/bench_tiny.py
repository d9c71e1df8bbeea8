"""Tiny cells for tests on the CPU: the real cells' files, cut to a size a
test run holds, driven through ``run.main`` without the look for chips."""
from __future__ import annotations

import copy
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[2] / "bench"
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 500, "padded_vocab": 512}


def tiny_cell(name: str, bm=None) -> H.Cell:
    cell = H.Cell(name, bm)
    cfg = copy.deepcopy(cell.config)
    cfg["model"].update(TINY_MODEL)
    if cfg["kind"] == "train":
        cfg.update(global_batch=4, seq_len=32, mesh="1x1", microbatch=0)
        cfg["optimizer"].update(warmup_steps=2)
    else:
        cfg.update(slots=4, max_seq=64)
        tr = copy.deepcopy(cell.traffic)
        tr["arrivals"]["rate_per_s"] = 4.0
        tr["prompt_len"].update(median=8, min=4, max=16)
        tr["output_len"].update(median=6, min=4, max=12)
        cell.traffic = tr
        # a logit gap is absolute, and this model's logits spread over a
        # fifth of the cell's: on the CPU the program reads about 2e-4 here
        # and the control 6e-3, so the limit sits between them as the
        # cell's sits between its own readings on the chip
        cell.limits = {"widest_logit_gap": 1e-3}
    cell.config = cfg
    cell.chips = 1
    return cell


def run(cell: H.Cell, seed: int = 7, seconds: float = 2.0, trace: int = 0):
    import jax
    import run as R
    return R.main(["--workload", cell.name, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  cell=cell, devices=jax.devices()[:cell.chips])
