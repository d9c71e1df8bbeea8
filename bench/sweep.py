"""Find the serving cell's knee once: the highest arrival rate the engine
sustains without a growing backlog.  One process, one engine build per
rate, each rate a window of ``--seconds``.

    python bench/sweep.py --workload <cell> --rates 2,3,4 --seconds 30

Prints per rate the requests due, the backlog (requests queued, not yet
admitted) at the window's middle and close, the time to first token and
the tokens served.  The cell's traffic file then fixes its rate at about
four fifths of the knee.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402
import serve_cell as C  # noqa: E402


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()
    cell = H.Cell(args.workload)
    devs = H.require_chips(cell.chips)
    H.enable_compile_cache()
    clog = H.CompileLog()
    for rate in (float(x) for x in args.rates.split(",")):
        r = C.run(cell, seed=args.seed, seconds=args.seconds, trace=False,
                  devs=devs, clog=clog, rate_override=rate)
        print(json.dumps({"rate_per_s": rate, "due": r["attempted"],
                          "unfinished": r["failed"],
                          "backlog_mid_close": r["backlog"],
                          "ttft_p50_ms": r["ttft_p50_ms"],
                          "ttft_p95_ms": r["e2e"]["serve_ttft_p95_ms"],
                          "itl_p95_ms": r["e2e"]["serve_itl_p95_ms"],
                          "tokens": r["tokens"], "ran_s": r["ran_s"]}),
              flush=True)


if __name__ == "__main__":
    main()
