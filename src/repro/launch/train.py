"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch internlm2-1.8b \
        --reduced --steps 50 --batch 8 --seq 128

``--distributed`` initializes ``jax.distributed`` before the mesh is built.
Compiled steps persist in JAX's compilation cache (``launch/cache.py``).
"""
from __future__ import annotations

import argparse
import json


def main():
    from repro.core.schedule import SCHEDULES
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internlm2-1.8b")
    ap.add_argument("--reduced", action="store_true",
                    help="tiny same-family config (CPU demo)")
    ap.add_argument("--schedule", default="oases", choices=list(SCHEDULES))
    ap.add_argument("--no-fine-remat", dest="fine_remat",
                    action="store_false")
    ap.add_argument("--planner", action="store_true",
                    help="per-layer TMP degrees from the ILP (factored mesh)")
    ap.add_argument("--calibrate", action="store_true", default=True,
                    help="profile-guided --planner inputs (the DEFAULT: "
                         "HWConfig.from_measurements via the per-host "
                         "calibration cache)")
    ap.add_argument("--no-calibrate", dest="calibrate",
                    action="store_false",
                    help="plan with the stock chip numbers instead of "
                         "on-device calibration")
    ap.add_argument("--tmp-layout", default="auto",
                    choices=["auto", "1d", "2d"],
                    help="partition layout: 1d (classic), 2d (hybrid "
                         "model_x*model_y), auto (follow the mesh; the "
                         "planner searches both spaces)")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (prepends a 'pipe' mesh "
                         "axis; composes with --mesh dxm specs)")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="interleaved-1F1B virtual stages per device")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="1F1B microbatch count / grad-accumulation steps "
                         "(0 = auto)")
    ap.add_argument("--seq-shard", type=int, default=1,
                    help="ring-attention sequence shards per attention "
                         "layer (power of two; must equal the mesh model "
                         "group size — DESIGN.md §12)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="checkpoints/run0")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--mesh", default="auto",
                    help="auto | dxm (e.g. 2x4) | production | multipod")
    ap.add_argument("--plan", default="", metavar="plan.json",
                    help="execute a ParallelPlan file (planner output / "
                         "--save-plan); overrides the legacy parallelism "
                         "flags in one shot")
    ap.add_argument("--save-plan", default="", metavar="out.json",
                    help="write the resolved ParallelPlan (desugared "
                         "flags or the ILP decision under --planner) for "
                         "later --plan runs")
    ap.add_argument("--planner-schedules", default="current",
                    choices=["current", "auto"],
                    help="--planner search space: degrees under the "
                         "--schedule ('current') or the full per-layer "
                         "(degree, schedule) space of the paper ('auto')")
    ap.add_argument("--planner-seq", default="none",
                    choices=["none", "auto"],
                    help="--planner seq axis: 'auto' lets the ILP shard "
                         "long sequences over KV rings per attention "
                         "layer instead of (only) sharding heads")
    ap.add_argument("--distributed", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--elastic", action="store_true",
                    help="run under the ElasticSupervisor: faults trigger "
                         "ILP replanning + in-memory relayout instead of "
                         "a crash (runtime/elastic.py)")
    ap.add_argument("--hosts", type=int, default=1,
                    help="elastic: host count the devices split across "
                         "(host h owns the contiguous device slice)")
    ap.add_argument("--max-replans", type=int, default=3)
    ap.add_argument("--max-restarts", type=int, default=3)
    ap.add_argument("--restart-backoff", type=float, default=0.05,
                    help="restart backoff base seconds (exponential)")
    ap.add_argument("--inject-fail", default="", metavar="STEPS",
                    help="chaos: comma-separated steps raising a generic "
                         "worker failure (restart-from-checkpoint path)")
    ap.add_argument("--inject-host-loss", default="", metavar="STEP:HOST",
                    help="chaos: lose HOST at STEP (comma-separated pairs; "
                         "elastic replan + relayout path)")
    ap.add_argument("--inject-link-degrade", default="", metavar="STEP:BW",
                    help="chaos: degrade inter-node bandwidth to BW "
                         "bytes/s at STEP")
    ap.add_argument("--inject-ckpt-corrupt", default="", metavar="STEPS",
                    help="chaos: bit-flip the checkpoint written at these "
                         "steps (intact-fallback path)")
    ap.add_argument("--inject-ckpt-fail", type=int, default=0,
                    metavar="N",
                    help="chaos: first N checkpoint writes raise a "
                         "transient OSError (async retry path)")
    ap.add_argument("--telemetry", default="", metavar="DIR",
                    help="write structured telemetry (JSONL) under DIR; "
                         "render with `python -m repro.obs.report DIR`")
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
        # global install: planner/serving instrumentation reaches it via
        # obs.get_recorder(); console=print keeps the familiar log lines
        telemetry = obs.configure(args.telemetry,
                                  flush_every=args.telemetry_flush,
                                  console=print)

    def _steps(spec):
        return tuple(int(s) for s in spec.split(",") if s)

    def _pairs(spec, second=int):
        return tuple((int(a), second(b))
                     for a, b in (p.split(":") for p in spec.split(",")
                                  if p))

    if args.distributed:
        import jax
        jax.distributed.initialize()

    import dataclasses

    from repro.configs.base import TrainHParams
    from repro.configs.registry import get_config
    from repro.core.axes import mesh_info
    from repro.launch.mesh import resolve_launch
    from repro.runtime import Trainer

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced().replace(dtype="float32")

    hp = TrainHParams(schedule=args.schedule, fine_remat=args.fine_remat,
                      learning_rate=args.lr, total_steps=args.steps,
                      warmup_steps=max(args.steps // 20, 1),
                      use_planner=args.planner, tmp_layout=args.tmp_layout,
                      microbatch=args.microbatch,
                      virtual_stages=args.virtual_stages,
                      seq_shard=args.seq_shard)
    # the ONE desugaring path (launch/mesh.py): legacy flags or a --plan
    # file become (mesh, ParallelPlan, projected hp)
    mesh, pplan, hp = resolve_launch(cfg, hp, mesh=args.mesh, pp=args.pp,
                                     plan_file=args.plan)
    if args.planner and not args.plan:
        from repro.configs.base import ShapeConfig
        from repro.core.planner import plan as plan_search
        from repro.core.planner.costmodel import V5E
        info = mesh_info(mesh)
        if args.calibrate:
            # profile-guided by default: the cost model's chip terms come
            # from measurements (cached per host), the cluster shape from
            # the resolved mesh; --no-calibrate keeps the spec-sheet V5E
            from repro.core.planner.calibrate import calibrated_hw, describe
            hw = calibrated_hw(n_chips=info.mesh.size)
            print(f"planner: calibrated hw {describe(hw)}")
        else:
            hw = V5E
        # plan for the workload actually being trained, not a fixed table
        shape = ShapeConfig("cli", args.seq, args.batch, "train")
        pr = plan_search(cfg, shape, hp, hw,
                         layout=args.tmp_layout,
                         options=tuple(n for n in (2, 4, 8, 16)
                                       if n <= info.tp) or (info.tp,),
                         schedules="auto"
                         if args.planner_schedules == "auto" else None,
                         seq=args.planner_seq)
        print(f"planner: {pr.summary()}")
        if info.factored or pr.plan.planned_degrees is None:
            # mixed degrees need the factored mesh; a mesh-following plan
            # (uniform degrees — incl. ring seq-shard plans) runs anywhere
            pplan = dataclasses.replace(pplan, layers=pr.plan.layers)
        else:
            print("planner: mesh is not factored — plan shown for "
                  "inspection only, training uses the uniform layout")
    if args.save_plan:
        pplan.save(args.save_plan)
        print(f"[plan] wrote {args.save_plan}: {pplan.summary()}")
    from repro.runtime import FailureInjector
    injector = FailureInjector(
        fail_at_steps=_steps(args.inject_fail),
        host_loss=_pairs(args.inject_host_loss),
        link_degrade=_pairs(args.inject_link_degrade, float),
        ckpt_fail_saves=args.inject_ckpt_fail,
        corrupt_at_steps=_steps(args.inject_ckpt_corrupt))

    if args.elastic:
        import jax

        from repro.configs.base import ShapeConfig
        from repro.runtime import ElasticConfig, ElasticSupervisor, Topology
        from repro.runtime import elastic as el
        ndev = len(jax.devices())
        hosts = max(args.hosts, 1)
        if ndev % hosts:
            raise SystemExit(f"--hosts {hosts} does not divide the "
                             f"{ndev} visible devices")
        topo = Topology(n_hosts=hosts, chips_per_host=ndev // hosts)

        def make_trainer(topology, plan):
            m = el.mesh_for(topology, plan or pplan)
            return Trainer(cfg, m, hp, global_batch=args.batch,
                           seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                           injector=injector,
                           plan=plan if plan is not None else pplan,
                           telemetry=telemetry)

        sup = ElasticSupervisor(
            make_trainer, topology=topo, cfg=cfg,
            shape=ShapeConfig("cli", args.seq, args.batch, "train"),
            hp=hp,
            econfig=ElasticConfig(max_replans=args.max_replans,
                                  max_restarts=args.max_restarts,
                                  backoff_s=args.restart_backoff),
            telemetry=telemetry)
        res = sup.run(args.steps, ckpt_every=args.ckpt_every,
                      seed=args.seed)
        if telemetry is not None:
            telemetry.close()
        print(json.dumps({
            "final_step": res["final_step"],
            "first_loss": res["losses"][0], "last_loss": res["losses"][-1],
            "slow_steps": len(res["slow_steps"]),
            "events": [e.describe() for e in res["events"]],
            "replans": res["replans"], "restarts": res["restarts"],
            "surviving_chips": res["topology"].n_chips,
        }, indent=1))
        return

    trainer = Trainer(cfg, mesh, hp, global_batch=args.batch,
                      seq_len=args.seq, ckpt_dir=args.ckpt_dir,
                      injector=injector, plan=pplan, telemetry=telemetry)
    res = trainer.train(args.steps, ckpt_every=args.ckpt_every,
                        seed=args.seed)
    if telemetry is not None:
        telemetry.close()
    print(json.dumps({
        "final_step": res["final_step"],
        "first_loss": res["losses"][0], "last_loss": res["losses"][-1],
        "slow_steps": len(res["slow_steps"]),
    }, indent=1))


if __name__ == "__main__":
    main()
