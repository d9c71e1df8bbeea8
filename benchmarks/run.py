"""Benchmark harness entrypoint — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = the primary latency
of the row where defined, else the modeled iteration time), then a readable
JSON dump per table to results/bench_report.json.

``--dry-run``: exercise every driver's modeled path but skip the measured
steps (the fig6 subprocess and the measured-speed tier, the only slow
steps) — the CI smoke that keeps the benchmark drivers from bit-rotting.

Without ``--dry-run`` the run additionally emits the MEASURED section:
wall-clock tokens/s per (config x schedule) grid point on this host's
devices, paired with the calibrated cost model's prediction for the same
point (benchmarks/measured.py).  ``bench_diff.py --ranking`` gates on the
modeled-vs-measured ranking agreement — the loop that stops the perf gate
from grading its own homework.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--dry-run", action="store_true",
                    help="modeled paths only; skip the measured fig6 "
                         "subprocess (CI smoke)")
    ap.add_argument("--tag", default="local",
                    help="label for the machine-readable BENCH_<tag>.json "
                         "written at the repo root (perf trajectory — "
                         "future PRs diff against it)")
    ap.add_argument("--measured-points", type=int, default=0,
                    help="truncate the measured-tier grid to the first N "
                         "points (0 = full grid; smokes use 1)")
    ap.add_argument("--measured-iters", type=int, default=3,
                    help="timed iterations per measured-tier point")
    ap.add_argument("--telemetry", default="", metavar="DIR",
                    help="measured tier: write a structured JSONL trace "
                         "(per-point timings) "
                         "under DIR — CI uploads it next to BENCH_<tag>")
    args = ap.parse_args()
    root = os.path.join(os.path.dirname(__file__), "..")
    sys.path.insert(0, os.path.abspath(root))       # the benchmarks package
    sys.path.insert(0, os.path.join(root, "src"))
    from benchmarks import (fig2_breakdown, fig4_end_to_end, fig6_costmodel,
                            fig7_scaling, roofline_report, table2_device_eff,
                            table3_ablation, table6_planner)
    from benchmarks.common import ensure_results_dir

    report = {}
    print("name,us_per_call,derived")

    rows = fig2_breakdown.run()
    report["fig2_breakdown"] = rows
    for r in rows:
        print(f"fig2/{r['model']}/{r['schedule']},{r['iter_ms']*1e3:.0f},"
              f"comm_share={r['comm_share']}")

    rows = fig4_end_to_end.run()
    report["fig4_end_to_end"] = rows
    report["fig4_summary"] = fig4_end_to_end.summarize(rows)
    for r in rows:
        for sched, norm in r["normalized"].items():
            tps = r["tokens_per_s"][sched]
            us = 1e6 * r["batch"] * 1024 / max(tps, 1e-9)
            print(f"fig4/{r['model']}/{sched},{us:.0f},norm={norm}")

    rows = table2_device_eff.run()
    report["table2_device_eff"] = rows
    for r in rows:
        print(f"table2/{r['model']},0,meg={r['megatron']}"
              f";oases={r['oases']};ratio={r['ratio']}")

    rows = table3_ablation.run()
    report["table3_ablation"] = rows
    for r in rows:
        s = r["speedup_vs_megatron"]
        print(f"table3/{r['model']},0," + ";".join(
            f"{k}={v}" for k, v in s.items()))

    rows = table6_planner.run()
    report["table6_planner"] = rows
    for r in rows:
        print(f"table6/{r['model']},{r['optim_time_ms']*1e3:.0f},"
              f"plan={r['planned'].replace(',', ' ')}")

    rows = fig7_scaling.run()
    report["fig7_scaling"] = rows
    for r in rows:
        print(f"fig7/{r['model']}/{r['schedule']}/{r['chips']},0,"
              f"eff={r['scaling_eff']}")

    measured = None
    if args.dry_run:
        report["fig6_costmodel"] = {"skipped": "dry-run"}
        print("fig6/spearman,0,SKIPPED(dry-run)")
        report["measured"] = {"skipped": "dry-run"}
        print("measured/tier,0,SKIPPED(dry-run)")
    else:
        try:
            f6 = fig6_costmodel.run()
            report["fig6_costmodel"] = f6
            print(f"fig6/spearman,0,rho={f6['spearman']}")
            for p in f6["points"]:
                print(f"fig6/{p['strategy'].replace(',', ' ')},"
                      f"{p['measured_ms']*1e3:.0f},"
                      f"pred_ms={p['predicted_ms']}")
        except Exception as e:  # measured path needs the 8-dev subprocess
            report["fig6_costmodel"] = {"error": str(e)[:500]}
            print("fig6/spearman,0,ERROR")
        # measured-speed tier (ROADMAP item 3): wall-clock tokens/s per
        # (config x schedule), paired with the calibrated model's view of
        # the same point.  A failure here must fail the run — a silently
        # missing measured section would let the modeled gate grade its
        # own homework again.
        from benchmarks import measured as measured_mod
        measured = measured_mod.run(points=args.measured_points,
                                    iters=args.measured_iters,
                                    telemetry=args.telemetry)
        report["measured"] = measured
        for p in measured["points"]:
            print(f"measured/{p['key'].replace(',', ' ')},"
                  f"{p['measured_ms']*1e3:.0f},"
                  f"tok_s={p['measured_tok_s']}"
                  f";modeled_tok_s={p['modeled_tok_s']}")

    rows = roofline_report.run()
    report["roofline"] = rows
    for r in rows:
        if r["status"] != "OK":
            print(f"roofline/{r['arch']}/{r['shape']},0,{r['status']}")
            continue
        dom_us = 1e6 * max(r["compute_s"], r["memory_s"], r["collective_s"])
        print(f"roofline/{r['arch']}/{r['shape']},{dom_us:.0f},"
              f"dom={r['dominant']};frac={r['roofline_fraction']}")

    # joint PP x TMP planner decisions on the fixture HWConfigs (modeled;
    # the bubble fraction is the pipeline's idle share of the iteration)
    from repro.configs.base import TrainHParams
    from repro.configs.gpt_oases import PAPER_TABLE4, paper_shape
    from repro.core.planner import COMMODITY_25GBE, NVLINK_BOX, plan_joint
    cfg, _t, _d, gb = PAPER_TABLE4["gpt-h8192"]
    joint = {}
    for fixture, hw in (("commodity_25gbe", COMMODITY_25GBE),
                        ("nvlink_box", NVLINK_BOX)):
        r = plan_joint(cfg, paper_shape(gb), TrainHParams(schedule="oases"),
                       hw, options=(16,))
        joint[fixture] = {
            "pp": r.pp, "n_micro": r.n_micro,
            "degrees": [list(d) if isinstance(d, tuple) else d
                        for d in r.degrees],
            "predicted_ms": round(r.predicted_s * 1e3, 3),
            "tmp_only_ms": round(r.tmp_only_s * 1e3, 3),
            "bubble_fraction": round(r.bubble_fraction, 4),
            "p2p_ms": round(r.p2p_s * 1e3, 3),
        }
        print(f"joint/{fixture},{r.predicted_s*1e6:.0f},"
              f"pp={r.pp};bubble={r.bubble_fraction:.3f}")
    report["joint_pp_planner"] = joint

    # per-layer (degree, schedule) executable-plan search — the paper's
    # REAL search space.  Pins the mixed plan of the memory-cliff regime
    # on the commodity fixture against the best uniform schedule (the
    # tentpole golden, tests/test_planner_golden.py::MIXED_CASES).
    from repro.configs.base import SHAPES
    from repro.configs.registry import get_config
    from repro.core.plan import SCHEDULES
    from repro.core.planner import plan
    mixed = {}
    for arch, cap in (("llama-3.2-vision-11b", 18.5e9),
                      ("granite-moe-3b-a800m", 5.6e9)):
        mcfg = get_config(arch)
        mhp = TrainHParams()
        r = plan(mcfg, SHAPES["train_4k"], mhp, COMMODITY_25GBE,
                 options=(8, 16), mem_cap=cap, schedules="auto",
                 time_limit=30.0)
        uni = {s: plan(mcfg, SHAPES["train_4k"], mhp, COMMODITY_25GBE,
                       options=(8, 16), mem_cap=cap, schedules=(s,),
                       time_limit=30.0).predicted_s for s in SCHEDULES}
        best_s = min(uni, key=uni.get)
        mixed[arch] = {
            "plan": r.plan.summary(),
            "predicted_ms": round(r.predicted_s * 1e3, 3),
            "best_uniform": best_s,
            "best_uniform_ms": round(uni[best_s] * 1e3, 3),
            "mixed_speedup": round(uni[best_s] / r.predicted_s, 4),
        }
        print(f"planx/{arch},{r.predicted_s*1e6:.0f},"
              f"speedup_vs_{best_s}={mixed[arch]['mixed_speedup']}")
    report["mixed_schedule_planner"] = mixed

    # serving latency planner decisions (modeled per-token decode latency;
    # plan(objective="latency") over (dx, dy, pp) serving meshes)
    from repro.configs.base import ShapeConfig
    serve_shape = ShapeConfig("serve_b8_4k", 4096, 8, "decode")
    serving = {}
    for fixture, hw in (("commodity_25gbe", COMMODITY_25GBE),
                        ("nvlink_box", NVLINK_BOX)):
        r = plan(cfg, serve_shape, TrainHParams(schedule="fused"), hw,
                 options=(16,), objective="latency")
        serving[fixture] = {
            "degree": list(r.degree) if isinstance(r.degree, tuple)
            else r.degree,
            "pp": r.pp, "n_micro": r.n_micro,
            "predicted_ms": round(r.predicted_s * 1e3, 4),
            "tok_per_s": round(r.tok_per_s, 1),
            "tmp_only_ms": round(r.tmp_only_s * 1e3, 4),
        }
        print(f"serve/{fixture},{r.predicted_s*1e6:.0f},"
              f"pp={r.pp};tok_per_s={r.tok_per_s:.0f}")
    report["serving_latency_planner"] = serving

    # serving-path throughput ladder (dense -> paged -> +prefix -> +spec)
    # on the real engine: deterministic step counts feed the BENCH gate,
    # wall-clock tokens/s and TTFT stay in the report (host-dependent).
    # Runs under --dry-run too — the engine ladder is the CI smoke that
    # keeps the serving path from bit-rotting.
    from benchmarks import serving_throughput
    st = serving_throughput.run()
    report["serving_throughput"] = st
    for name, row in st["variants"].items():
        print(f"serve_tp/{name},{row['ttft_ms']*1e3:.0f},"
              f"steps={row['steps']};tok_s={row['tok_per_s']}")

    d = ensure_results_dir()
    with open(os.path.join(d, "bench_report.json"), "w") as f:
        json.dump(report, f, indent=1)
    print("# wrote results/bench_report.json", file=sys.stderr)

    # machine-readable perf trajectory at the repo root: the numbers a
    # future PR diffs against (tokens/s per schedule, planner decisions,
    # bubble fraction)
    bench = {
        "tag": args.tag,
        "time": time.time(),
        "dry_run": bool(args.dry_run),
        "tokens_per_s": {r["model"]: r["tokens_per_s"]
                         for r in report["fig4_end_to_end"]},
        "schedule_speedup_vs_megatron": {
            r["model"]: r["speedup_vs_megatron"]
            for r in report["table3_ablation"]},
        "planner_decisions": {r["model"]: r["planned"]
                              for r in report["table6_planner"]},
        "joint_pp_planner": joint,
        "serving_latency_planner": serving,
        "mixed_schedule_planner": mixed,
        "serving_throughput": serving_throughput.bench_fields(st),
    }
    if measured is not None:
        bench["measured"] = measured
    out = os.path.abspath(os.path.join(root, f"BENCH_{args.tag}.json"))
    with open(out, "w") as f:
        json.dump(bench, f, indent=1)
    print(f"# wrote {out}", file=sys.stderr)


if __name__ == "__main__":
    main()
