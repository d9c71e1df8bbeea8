"""Readings that set a cell's limits: the program's on many seeds, the
control's (the reference in float8), and each planted fault's, all at the
cell's own size, in one process on the cell's chips.

    python bench/readings.py --workload <cell> --seeds 1,2,3 \
        [--control-seeds 1,2,3] [--faults half_batch --fault-seeds 1,2,3] \
        [--seconds 20] --out readings.jsonl

Training cells run no window: the first steps and the reference.  Serving
cells run a window of ``--seconds`` at the cell's own load, and read the
control over the same sampled requests.  Each reading is one JSON line;
the cell's module (``<kind>_cell.readings``) says what it holds.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402


def ints(s: str):
    return [int(x) for x in s.split(",") if x]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=ints, default=[])
    ap.add_argument("--control-seeds", type=ints, default=[])
    ap.add_argument("--faults", default="")
    ap.add_argument("--fault-seeds", type=ints, default=[])
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    cell = H.Cell(args.workload)
    devs = H.require_chips(cell.chips)
    H.enable_compile_cache()
    clog = H.CompileLog()

    def emit(**rec):
        rec["t"] = time.time()
        with open(args.out, "a") as out:
            out.write(json.dumps(rec) + "\n")
        H.log(json.dumps(rec))

    faults = [f for f in args.faults.split(",") if f]
    cell.runner().readings(cell, args, faults, devs=devs, clog=clog,
                           emit=emit)


if __name__ == "__main__":
    main()
