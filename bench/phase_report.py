"""One traced run of a cell, split by the program's own phases.

    python3 bench/phase_report.py --workload <cell> --seed <n> --seconds <s>

Runs ``run.py``'s ``--trace 1`` path unchanged (its result line is printed
as usual) and prints what its readers were given (``ctx["phases"]``, the
split of the same trace by ``phases.py``) as tables: chip 0's time per pass
(``fwd``, ``bwd``, ``opt``, ``other``) and per named scope, collective and
exposed time per pass and ``tmp.*`` scope, idle time per program span, and,
for a serving cell, the requests' queue wait and prompt path and the slots'
occupancy from the engine's own stamps and counters; and the collector's
long pauses, to tell a host stall from device work.  The tables go to
stderr; the last line of stdout is one JSON object with each number of
``phases.READERS``, the sums that check them, and the window's end-to-end
numbers, taken with the profiler on for part of it.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import harness as H  # noqa: E402  (its import marks the process start)
import phases as P  # noqa: E402


class Collections:
    """The collector's full (generation 2) passes and any other pass over
    10 ms: (generation, seconds since the process start, seconds), so a
    long host gap can be matched with one."""

    def __init__(self):
        self.t = None
        self.pauses = []
        gc.callbacks.append(self)

    def __call__(self, phase, info):
        if phase == "start":
            self.t = time.perf_counter()
        elif self.t is not None:
            d = time.perf_counter() - self.t
            if info["generation"] == 2 or d > 0.01:
                self.pauses.append([info["generation"], self.t - H.T0, d])


def main(argv=None, *, cell: H.Cell = None, devices=None):
    """``cell`` and ``devices`` as for ``run.main``."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    cell = cell if cell is not None else H.Cell(args.workload)
    kind = cell.config["kind"]
    collections = Collections()
    import run as R
    r = R.main(["--workload", cell.name, "--seed", str(args.seed),
                "--seconds", str(args.seconds), "--trace", "1"],
               cell=cell, devices=devices)
    ctx = r["ctx"]
    ph, red = ctx["phases"], ctx["trace"]
    out = {"workload": cell.name, "seed": args.seed,
           "correct": r["correct"], "phases": P.read_all(ctx),
           "per_layer": {k: v["value"] for k, v in r["per_layer"].items()},
           "e2e_with_trace": r["e2e"],
           "longest_idle_gaps_ms": [[k, 1e3 * v] for k, v in
                                    ph["longest_idle_gaps"]],
           "collector_pauses": [[g, t, 1e3 * d]
                                for g, t, d in collections.pauses]}
    if kind == "train":
        steps = ctx["steps"]
        H.log(P.tables(ph, steps, "step"))
        out.update(
            steps=steps,
            busy_ms_by_pass={k: 1e3 * v / steps
                             for k, v in ph["busy_s_by_pass"].items()},
            busy_ms_by_scope={k: 1e3 * v / steps
                              for k, v in ph["busy_s_by_scope"].items()},
            step_device_ms=1e3 * red["busy_s_dev0"] / steps,
            exposed_ms_by_pass={k: 1e3 * v / steps
                                for k, v in ph["exposed_s_by_pass"].items()},
            exposed_ms=1e3 * red["exposed_collective_s"] / steps,
            collective_ms_by_pass={
                k: 1e3 * v / steps
                for k, v in ph["collective_s_by_pass"].items()},
            collective_ms=1e3 * red["collective_s"] / steps,
            collective_by_scope_ms=[
                [p, s, 1e3 * c / steps, 1e3 * x / steps]
                for p, s, c, x in ph["collective_by_scope"]])
    else:
        n = max(ctx["ticks_traced"], 1)
        H.log(P.tables(ph, n, "traced tick"))
        traced = ctx["traced_tick_s"]
        out.update(
            ticks=ctx["ticks"], ticks_traced=ctx["ticks_traced"],
            slot_ticks=ctx["slot_ticks"],
            finished=len(ctx["request_times"]),
            stamps_ordered=all(s <= a <= f for s, a, f in
                               ctx["request_times"]),
            idle_ms=1e3 * ph["idle_s_dev0"],
            idle_pct=100.0 * ph["idle_s_dev0"] / red["window_s"],
            idle_by_program_span_ms_per_tick=[
                [k, 1e3 * v / n] for k, v in ph["idle_by_program_span"]],
            traced_tick_ms=1e3 * sum(traced) / max(len(traced), 1),
            traced_ticks_timed=len(traced))
    print(json.dumps(out), flush=True)
    return out


if __name__ == "__main__":
    main()
