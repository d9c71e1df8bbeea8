"""Serving launcher: batched requests through the continuous-batching
engine, on single-device or TMP / pipeline-parallel meshes.

    # single device (CPU smoke)
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --requests 8 --slots 4

    # 2-way TMP with fused collective-matmul decode
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --mesh 1x2 --schedule fused

    # 2 pipeline stages x 2-way TMP (decode micro-steps stream through
    # the stages)
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --pp 2 --mesh 1x2 --schedule fused

    # execute a saved ParallelPlan (e.g. train.py --save-plan / the
    # latency planner's .plan) — one file instead of the flag soup
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --plan plan.json

    # production-throughput path: paged KV blocks + prefix reuse +
    # speculative decoding (greedy, token-identical to undrafted decode)
    PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b \
        --reduced --paged --page-size 8 --prefix-cache \
        --draft internlm2-1.8b --spec-k 3
"""
from __future__ import annotations

import argparse
import json

import numpy as np


def main():
    from repro.core.schedule import SCHEDULES
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--prefill-len", type=int, default=0,
                    help="longest admissible prompt (engine admission "
                         "contract); 0 = derive max_seq // 2")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--schedule", default="oases", choices=list(SCHEDULES),
                    help="TMP overlap schedule for the decode matmuls "
                         "('fused' rings the collectives over the slot "
                         "batch)")
    ap.add_argument("--mesh", default="auto",
                    help="auto | dxm (e.g. 1x4) | dxm1xm2 (2D hybrid, "
                         "e.g. 1x2x2); --pp prepends a 'pipe' stage axis")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages: decode micro-steps "
                         "stream through the stages (stage s decodes "
                         "micro-group g while stage s-1 decodes g+1)")
    ap.add_argument("--tmp-layout", default="auto",
                    choices=["auto", "1d", "2d"])
    ap.add_argument("--seq-shard", type=int, default=1,
                    help="recorded in the resolved plan for provenance; "
                         "decode itself always serves head-sharded (the "
                         "KV ring is a training/prefill layout)")
    ap.add_argument("--decode-micro", type=int, default=0,
                    help="decode micro-group count on a pipeline mesh "
                         "(0 = auto: pp * virtual stages)")
    ap.add_argument("--plan", default="", metavar="plan.json",
                    help="execute a ParallelPlan file (e.g. from train.py "
                         "--save-plan or the latency planner); overrides "
                         "the legacy parallelism flags in one shot")
    ap.add_argument("--save-plan", default="", metavar="out.json",
                    help="write the resolved serving ParallelPlan")
    ap.add_argument("--print-plan", default="",
                    choices=["", "commodity", "nvlink"],
                    help="print the latency-objective serving plan "
                         "(plan(objective='latency')) for this arch on a "
                         "fixture HWConfig before serving")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV cache: fixed-size blocks in a shared "
                         "page pool with per-slot block tables "
                         "(serving/paged_cache.py); admission becomes "
                         "reservation-based with cache-full backpressure")
    ap.add_argument("--pages", type=int, default=0,
                    help="physical pages in the pool incl. the null page "
                         "(0 = auto: every slot can still reach max_seq)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (max_seq must divide evenly)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="reuse cached prompt blocks across requests "
                         "(block-granular hashing, refcounted pages, COW "
                         "on first divergent write); requires --paged")
    ap.add_argument("--draft", default="", metavar="CONFIG",
                    help="draft model config for speculative decoding "
                         "(e.g. mamba2-130m; reduced alongside --reduced); "
                         "pair with --spec-k")
    ap.add_argument("--spec-k", type=int, default=0,
                    help="draft tokens proposed per speculative round "
                         "(greedy acceptance is token-identical to "
                         "undrafted decode; plan_serving picks k per "
                         "cluster fixture)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--telemetry", default="", metavar="DIR",
                    help="write structured telemetry (JSONL) under DIR: "
                         "TTFT, per-token decode latency, queue depth, "
                         "slot occupancy; render with `python -m "
                         "repro.obs.report DIR`")
    ap.add_argument("--telemetry-flush", type=int, default=64,
                    metavar="N",
                    help="JSONL records buffered between file flushes "
                         "(must be positive; 1 = write-through)")
    args = ap.parse_args()

    telemetry = None
    if args.telemetry:
        from repro import obs
        if args.telemetry_flush <= 0:
            raise SystemExit(
                f"--telemetry-flush must be a positive number of records, "
                f"got {args.telemetry_flush} (use 1 for write-through)")
        telemetry = obs.configure(args.telemetry,
                                  flush_every=args.telemetry_flush,
                                  console=print)

    from repro.configs.base import TrainHParams
    from repro.configs.registry import get_config
    from repro.launch.mesh import resolve_launch
    from repro.serving import Request, ServingEngine

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced().replace(dtype="float32")

    if args.print_plan:
        from repro.configs.base import ShapeConfig
        from repro.core.planner import COMMODITY_25GBE, NVLINK_BOX, plan
        hw = COMMODITY_25GBE if args.print_plan == "commodity" else NVLINK_BOX
        shape = ShapeConfig("serve_cli", args.max_seq, args.slots, "decode")
        pr = plan(cfg, shape, TrainHParams(schedule=args.schedule), hw,
                  options=tuple(n for n in (2, 4, 8, 16)
                                if n <= hw.n_chips) or (hw.n_chips,),
                  objective="latency")
        print(f"latency planner ({args.print_plan}): {pr.summary()}")

    hp = TrainHParams(schedule=args.schedule, tmp_layout=args.tmp_layout,
                      seq_shard=args.seq_shard)
    mesh, pplan, hp = resolve_launch(cfg, hp, mesh=args.mesh, pp=args.pp,
                                     plan_file=args.plan,
                                     save_plan=args.save_plan,
                                     decode_micro=args.decode_micro)
    draft_cfg = None
    if args.draft:
        draft_cfg = get_config(args.draft)
        if args.reduced:
            draft_cfg = draft_cfg.reduced().replace(dtype="float32")
    eng = ServingEngine(cfg, mesh, slots=args.slots, max_seq=args.max_seq,
                        hp=hp, prefill_len=args.prefill_len or None,
                        plan=pplan, telemetry=telemetry,
                        paged=args.paged, pages=args.pages,
                        page_size=args.page_size,
                        prefix_cache=args.prefix_cache,
                        draft=draft_cfg, spec_k=args.spec_k)
    eng.load(seed=args.seed)

    rng = np.random.default_rng(args.seed)
    reqs = []
    for i in range(args.requests):
        hi = max(min(12, eng.prefill_len + 1), 2)
        plen = int(rng.integers(min(4, hi - 1), hi))
        r = Request(rid=i,
                    prompt=rng.integers(3, cfg.vocab_size, plen,
                                        dtype=np.int32),
                    max_new_tokens=args.max_new_tokens)
        reqs.append(r)
        eng.submit(r)
    stats = eng.run_until_drained()
    if telemetry is not None:
        telemetry.close()
    print(json.dumps({**stats,
                      "mesh": dict(mesh.shape),
                      "schedule": hp.schedule,
                      "plan": pplan.summary(),
                      "prefill_len": eng.prefill_len,
                      "sample_output": reqs[0].out_tokens[:8]}, indent=1))


if __name__ == "__main__":
    main()
