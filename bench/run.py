"""Run one cell of the benchmark once and print its result line.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process holds the cell's chips.  With ``--trace 0`` the result carries
the cell's end-to-end metrics, with ``--trace 1`` its per-layer metrics,
read from a profiler trace of part of the window.  The last line of stdout
is one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` ``breakdown``, and last ``checks``, each
number compared for ``correct`` beside its limit.  It exits non-zero and
prints no result where JAX finds no TPU or fewer chips than the cell asks
for.
"""
from __future__ import annotations

import argparse
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402  (its import marks the process start)


def main(argv=None, *, cell: H.Cell = None, devices=None) -> dict:
    """``cell`` and ``devices`` let a test drive a run on a cell of its own
    without the look for chips."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cell if cell is not None else H.Cell(args.workload)
    devs = devices if devices is not None else H.require_chips(cell.chips)
    where = H.enable_compile_cache()
    H.log(f"bench: {cell.name} seed {args.seed} seconds {args.seconds} "
          f"trace {args.trace} on {H.device_info(devs)}; compile cache "
          f"{where}")
    clog = H.CompileLog()
    r = cell.runner().run(cell, seed=args.seed, seconds=args.seconds,
                   trace=bool(args.trace), devs=devs, clog=clog)
    H.log(f"compiles: {clog.compiles} ({clog.seconds} s), cache "
          f"{clog.hits} hits / {clog.misses} misses")
    device = dict(H.device_info(devs), memory_peak_bytes=r["memory_peak_bytes"])
    if args.trace:
        metrics = r["per_layer"]
        device.update(busy_s=r["busy_s"], window_s=r["window_s"])
    else:
        units = {m["name"]: m["unit"] for m in cell.end_to_end()}
        metrics = {k: {"value": r["e2e"][k], "unit": u}
                   for k, u in units.items()}
    H.emit(correct=r["correct"], attempted=r["attempted"],
           failed=r["failed"], metrics=metrics, device=device,
           checks=r["checks"], breakdown=r.get("breakdown"))
    return r


if __name__ == "__main__":
    main()
