import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"
# ^ MUST precede any jax import: jax locks the platform and device count on
# first init.  Only the dry-run sees 512 placeholder CPU devices (never a
# chip, also in its sweep children); tests/benches see 1.

import argparse          # noqa: E402
import json              # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402

from repro.configs.base import SHAPES, TrainHParams, applicable_shapes  # noqa: E402
from repro.configs.registry import ASSIGNED, get_config, get_shape      # noqa: E402
from repro.core.axes import mesh_info                                   # noqa: E402
from repro.launch import hlo_cost                                       # noqa: E402
from repro.launch.mesh import make_factored_mesh, make_production_mesh  # noqa: E402
from repro.launch.steps import (input_specs, jit_train_step,           # noqa: E402
                                step_fn_for)

# TPU v5e chip constants (roofline targets; this container only compiles)
PEAK_FLOPS = 197e12          # bf16
HBM_BW = 819e9               # bytes/s
LINK_BW = 50e9               # bytes/s per ICI link
HBM_CAP = 16e9               # bytes


def model_flops_per_chip(cfg, shape, n_chips: int) -> float:
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        total = 6.0 * n_active * tokens          # fwd + bwd
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        total = 2.0 * n_active * tokens
    else:                                        # decode: one token per seq
        total = 2.0 * n_active * shape.global_batch
    return total / n_chips


def parse_degrees(spec: str):
    """'8,4x2,16' -> [8, (4, 2), 16] (validated; see launch/mesh.py)."""
    from repro.launch.mesh import parse_degrees as _parse
    return _parse(spec)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             schedule: str = "oases", fine_remat: bool = True,
             planner_degrees=None, seq_parallel: bool = False,
             seq_shard: int = 1,
             split: int = 2, microbatch: int = 0,
             mesh_shape: str = "", tmp_layout: str = "auto",
             pp: int = 1, virtual_stages: int = 1, hw=None,
             plan_file: str = "", save_plan: str = "",
             plan_only: bool = False) -> dict:
    cfg = get_config(arch)
    shape = get_shape(shape_name)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "schedule": schedule, "fine_remat": fine_remat,
        "planner": planner_degrees is not None,
        "tmp_layout": tmp_layout, "pp": pp,
    }
    if shape.name not in {s.name for s in applicable_shapes(cfg)}:
        rec["status"] = "SKIP"
        rec["reason"] = ("full-attention arch: long_500k requires "
                         "sub-quadratic attention (DESIGN.md)")
        return rec

    t0 = time.perf_counter()
    hp = TrainHParams(schedule=schedule, fine_remat=fine_remat,
                      seq_parallel=seq_parallel, seq_shard=seq_shard,
                      split=split,
                      microbatch=microbatch, tmp_layout=tmp_layout,
                      virtual_stages=virtual_stages)
    if plan_file or mesh_shape:
        # the shared plan-desugaring path (launch/mesh.py): an explicit
        # device grid or a ParallelPlan file.  mesh_shape is the
        # hillclimb lever: reshape the 256 chips (e.g. "32x8" = more DP,
        # less TMP; "16x8x2" = a 2D hybrid model grid; --pp prepends the
        # pipeline stage axis).  The baseline table always uses 16x16.
        from repro.launch.mesh import resolve_launch
        mesh, pplan, hp = resolve_launch(
            cfg, hp, mesh=mesh_shape or "auto", pp=pp,
            plan_file=plan_file, save_plan=save_plan,
            degrees=planner_degrees)
        planner_degrees = pplan.planned_degrees
        rec["mesh_shape"] = mesh_shape or "x".join(
            map(str, pplan.mesh_shape))
        rec["plan"] = pplan.summary()
    else:
        from repro.core.plan import ParallelPlan
        from repro.launch.mesh import mesh_signature
        if pp > 1:
            from repro.launch.mesh import make_pipeline_mesh
            # 256 chips: pp stages x dp x 16-way TMP
            if 256 % (pp * 16):
                raise ValueError(
                    f"--pp {pp} does not divide the 256-chip production "
                    f"mesh (pp x 16-way TMP must divide 256 — pick pp in "
                    f"1/2/4/8/16, or pass an explicit --mesh-shape)")
            mesh = make_pipeline_mesh(pp, 256 // (pp * 16), 16)
        else:
            mesh = (make_factored_mesh(multi_pod=multi_pod)
                    if planner_degrees
                    else make_production_mesh(multi_pod=multi_pod))
        mshape, maxes = mesh_signature(mesh)
        pplan = ParallelPlan.from_hparams(
            hp, cfg.num_layers, degrees=planner_degrees,
            mesh_shape=mshape, mesh_axes=maxes, pp=pp)
        rec["plan"] = pplan.summary()
        if save_plan:
            pplan.save(save_plan)
            print(f"[plan] wrote {save_plan}: {pplan.summary()}")
    info = mesh_info(mesh)
    rec["microbatch"] = microbatch
    if plan_only:
        # --save-plan/--plan round-trip smoke (CI): resolve + desugar only
        rec["status"] = "PLAN_ONLY"
        rec["n_chips"] = info.mesh.size
        return rec
    if hw is not None and shape.kind == "train":
        # profile-guided planning: feed the calibrated chip numbers to the
        # joint PP x TMP search and record its decision next to the
        # measured-HLO terms of this cell
        from repro.core.planner import plan_joint
        jp = plan_joint(cfg, shape, hp, hw, virtual_stages=virtual_stages)
        rec["calibrated_joint_plan"] = {
            "pp": jp.pp, "n_micro": jp.n_micro,
            "degrees": [list(d) if isinstance(d, tuple) else d
                        for d in jp.degrees],
            "predicted_ms": round(jp.predicted_s * 1e3, 3),
            "bubble_fraction": round(jp.bubble_fraction, 4),
        }
        print(f"calibrated joint plan: {jp.summary()}")
    inputs = input_specs(cfg, shape, mesh, hp, plan=pplan)
    fn = step_fn_for(cfg, shape, mesh, hp, plan=pplan)
    # donate params+opt (train) / kv-cache (decode): buffers alias in place
    donate = (0, 1) if shape.kind == "train" else \
        ((1,) if shape.kind == "decode" else ())
    jitted = (jit_train_step(fn, mesh, donate) if shape.kind == "train"
              else jax.jit(fn, donate_argnums=donate))
    with mesh:
        lowered = jitted.lower(*inputs)
        t_lower = time.perf_counter() - t0
        compiled = lowered.compile()
        t_compile = time.perf_counter() - t0 - t_lower
        mem = compiled.memory_analysis()
        print(mem)                              # proves it fits
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):   # jax 0.4.x returns [dict]
            ca = ca[0] if ca else {}
        print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
        hc = hlo_cost.analyze(compiled.as_text(), default_group=info.tp)

    n_chips = info.mesh.size
    terms = {
        "compute_s": hc.dot_flops / PEAK_FLOPS,
        "memory_s": hc.hbm_bytes / HBM_BW,
        "collective_s": hc.collective_link_bytes / LINK_BW,
    }
    dominant = max(terms, key=terms.get)
    mf = model_flops_per_chip(cfg, shape, n_chips)
    arg_b = getattr(mem, "argument_size_in_bytes", 0)
    tmp_b = getattr(mem, "temp_size_in_bytes", 0)
    out_b = getattr(mem, "output_size_in_bytes", 0)
    alias_b = getattr(mem, "alias_size_in_bytes", 0)
    rec.update({
        "status": "OK",
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "mem": {"argument_bytes": arg_b, "temp_bytes": tmp_b,
                "output_bytes": out_b, "alias_bytes": alias_b,
                "peak_est_bytes": arg_b + tmp_b + out_b - alias_b,
                "fits_16GB": bool(arg_b + tmp_b + out_b - alias_b < HBM_CAP)},
        "xla_cost": {k: ca.get(k, 0.0) for k in ("flops", "bytes accessed")},
        "hlo": hc.to_dict(),
        "terms_s": terms,
        "dominant": dominant,
        "model_flops_per_chip": mf,
        "useful_flops_ratio": mf / hc.dot_flops if hc.dot_flops else 0.0,
        "roofline_fraction": (
            mf / PEAK_FLOPS) / max(terms.values()) if max(terms.values()) else 0.0,
    })
    return rec


def _sweep(args):
    cells = []
    archs = args.arch.split(",") if args.arch else ASSIGNED
    shapes = args.shape.split(",") if args.shape else list(SHAPES)
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    for a in archs:
        for s in shapes:
            for m in meshes:
                cells.append((a, s, m))
    done = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    done[(r["arch"], r["shape"], r["mesh"],
                          r.get("schedule", "oases"))] = r
                except json.JSONDecodeError:
                    pass
    for a, s, m in cells:
        key = (a, s, m, args.schedule)
        if key in done and done[key].get("status") in ("OK", "SKIP") \
                and not args.force:
            print(f"[cached] {key} {done[key]['status']}")
            continue
        cmd = [sys.executable, "-m", "repro.launch.dryrun",
               "--arch", a, "--shape", s, "--mesh", m,
               "--schedule", args.schedule, "--out", args.out]
        if not args.fine_remat:
            cmd.append("--no-fine-remat")
        print(f"[run] {a} x {s} x {m} ...", flush=True)
        t0 = time.perf_counter()
        try:
            p = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout)
            tail = (p.stdout + p.stderr).strip().splitlines()[-3:]
            print(f"   -> rc={p.returncode} {time.perf_counter()-t0:.0f}s "
                  + (" | ".join(tail) if p.returncode else ""), flush=True)
            if p.returncode:
                with open(args.out, "a") as f:
                    f.write(json.dumps({
                        "arch": a, "shape": s, "mesh": m,
                        "schedule": args.schedule, "status": "ERROR",
                        "error": "\n".join(tail)}) + "\n")
        except subprocess.TimeoutExpired:
            print("   -> TIMEOUT", flush=True)
            with open(args.out, "a") as f:
                f.write(json.dumps({
                    "arch": a, "shape": s, "mesh": m,
                    "schedule": args.schedule, "status": "TIMEOUT"}) + "\n")


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--schedule", default="oases")
    ap.add_argument("--no-fine-remat", dest="fine_remat", action="store_false")
    ap.add_argument("--split", type=int, default=2)
    ap.add_argument("--seq-parallel", action="store_true")
    ap.add_argument("--seq-shard", type=int, default=1,
                    help="ring-attention sequence shards per attention "
                         "layer (power of two; must equal the mesh model "
                         "group size — DESIGN.md §12)")
    ap.add_argument("--degrees", default="",
                    help="comma-separated per-layer TMP degrees (planner "
                         "mode); 'AxB' entries are 2D, e.g. 8,4x2,16")
    ap.add_argument("--tmp-layout", default="auto",
                    choices=["auto", "1d", "2d"],
                    help="partition layout (1d classic / 2d hybrid / auto)")
    ap.add_argument("--microbatch", type=int, default=0,
                    help="force gradient-accumulation / 1F1B microbatch "
                         "count (0 = auto)")
    ap.add_argument("--mesh-shape", default="",
                    help="override single-pod mesh, e.g. 32x8")
    ap.add_argument("--pp", type=int, default=1,
                    help="pipeline-parallel stages (prepends a 'pipe' "
                         "axis to the mesh)")
    ap.add_argument("--virtual-stages", type=int, default=1,
                    help="interleaved-1F1B virtual stages per device")
    ap.add_argument("--calibrate", action="store_true", default=True,
                    help="profile-guided planner inputs (the DEFAULT: "
                         "HWConfig.from_measurements via the per-host "
                         "calibration cache)")
    ap.add_argument("--no-calibrate", dest="calibrate",
                    action="store_false",
                    help="skip on-device calibration; plan with the stock "
                         "chip numbers")
    ap.add_argument("--plan", default="", metavar="plan.json",
                    help="dry-run an executable ParallelPlan file "
                         "(overrides the legacy parallelism flags)")
    ap.add_argument("--save-plan", default="", metavar="out.json",
                    help="write the resolved ParallelPlan for later "
                         "--plan runs")
    ap.add_argument("--plan-only", action="store_true",
                    help="resolve the mesh + plan (and --save-plan/"
                         "--plan round-trip) without lowering/compiling "
                         "— the CI plan smoke")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--timeout", type=int, default=2400)
    ap.add_argument("--out", default="results/dryrun.jsonl")
    args = ap.parse_args()
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    if args.sweep:
        _sweep(args)
        return

    hw_cal = None
    if args.calibrate and not args.plan_only:
        # default-on profile-guided planning (cached per host;
        # --no-calibrate restores the stock chip numbers).  --plan-only
        # resolves meshes without planning, so it skips the profile.
        from repro.core.planner.calibrate import calibrated_hw, describe
        hw_cal = calibrated_hw()
        print("calibrated HWConfig (profile-guided planner inputs):")
        print(json.dumps(describe(hw_cal), indent=1))

    degrees = parse_degrees(args.degrees) if args.degrees else None
    meshes = (["single", "multi"] if args.mesh == "both" else [args.mesh])
    for m in meshes:
        try:
            rec = run_cell(args.arch, args.shape, multi_pod=(m == "multi"),
                           schedule=args.schedule, fine_remat=args.fine_remat,
                           planner_degrees=degrees, split=args.split,
                           seq_parallel=args.seq_parallel,
                           seq_shard=args.seq_shard,
                           microbatch=args.microbatch,
                           mesh_shape=args.mesh_shape,
                           tmp_layout=args.tmp_layout,
                           pp=args.pp,
                           virtual_stages=args.virtual_stages,
                           hw=hw_cal,
                           plan_file=args.plan, save_plan=args.save_plan,
                           plan_only=args.plan_only)
        except Exception:
            rec = {"arch": args.arch, "shape": args.shape, "mesh": m,
                   "schedule": args.schedule, "status": "ERROR",
                   "error": traceback.format_exc()[-2000:]}
            print(traceback.format_exc())
        with open(args.out, "a") as f:
            f.write(json.dumps(rec) + "\n")
        print(json.dumps({k: rec[k] for k in rec
                          if k not in ("hlo", "xla_cost")}, indent=1))


if __name__ == "__main__":
    main()
