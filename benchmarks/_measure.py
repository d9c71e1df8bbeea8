"""Subprocess body for the measured benchmarks: 8 virtual CPU devices,
times real train-step iterations across a grid of (model config x TMP
degree x schedule) points and prints one JSON dict.

Two tiers share the harness (pick with ``--tier``):

* ``fig6`` (default) — the cost-model Spearman grid: prints a flat
  ``{key: seconds}`` dict consumed by :mod:`benchmarks.fig6_costmodel`.
  On this single-core container the wall-clock signal across *sharding
  layouts alone* is flat (total FLOPs are constant and the core is
  shared), so the grid also varies the model config — the cost model must
  rank the full grid correctly, which is the property the Oases planner
  relies on (Appendix C).
* ``measured`` — the measured-speed bench tier (ROADMAP item 3): for each
  (config x schedule) point it reports BOTH wall-clock tokens/s and the
  calibrated cost model's prediction for the same point
  (``HWConfig.measure_fields`` run in-process on the same virtual
  devices), so ``bench_diff.py --ranking`` can gate modeled-vs-measured
  ranking agreement without any modeled number leaving this process.

All hot-path timing uses ``time.perf_counter()`` — ``time.time()`` is
non-monotonic and low-resolution, and an NTP slew mid-measurement
corrupts tokens/s silently."""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
os.environ["JAX_PLATFORMS"] = "cpu"   # a CPU-only tool: never take a chip
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp

from repro.core import compat
from repro.configs.base import (ArchConfig, GLOBAL_ATTN, ShapeConfig,
                                TrainHParams)
from repro.core.axes import mesh_info
from repro.launch import steps as steps_mod
from repro.models import params as prm
from repro.optim import adamw


def make_cfg(d_model, layers, d_ff):
    return ArchConfig(
        name=f"bench-d{d_model}-l{layers}-f{d_ff}", family="dense",
        num_layers=layers, d_model=d_model, num_heads=max(d_model // 64, 2),
        num_kv_heads=max(d_model // 128, 1), d_ff=d_ff, vocab_size=8192,
        head_dim=64, layer_pattern=(GLOBAL_ATTN,), dtype="float32")


# (cfg, seq, batch) grid — spans ~20x in FLOPs
GRID = [
    (make_cfg(256, 2, 1024), 128, 8),
    (make_cfg(256, 4, 1024), 256, 8),
    (make_cfg(384, 4, 1536), 256, 8),
    (make_cfg(512, 4, 2048), 256, 8),
    (make_cfg(512, 6, 2048), 256, 8),
    (make_cfg(512, 4, 2048), 512, 8),
    (make_cfg(768, 4, 3072), 256, 8),
    (make_cfg(768, 6, 3072), 512, 8),
]
STRATS = [(8, "megatron", False), (8, "oases", True), (4, "oases", True),
          (2, "oases", True)]
BASE_CFG = make_cfg(512, 4, 2048)

# measured tier (ROADMAP item 3): the schedule ranking is the claim under
# test, so every schedule runs at the same (config, degree) point; two
# configs ~8x apart in FLOPs anchor the ranking where the single-core
# wall clock has real signal.
MEASURED_SCHEDULES = ["megatron", "wang", "oases", "fused"]
MEASURED_GRID = [
    (make_cfg(256, 2, 1024), 128, 8, 4),
    (make_cfg(512, 4, 2048), 256, 8, 4),
]


def measure(cfg, seq, batch, tmp_degree, schedule, fine, iters=3):
    dp = 8 // tmp_degree
    mesh = compat.make_mesh((dp, tmp_degree), ("data", "model"))
    hp = TrainHParams(schedule=schedule, fine_remat=fine, microbatch=1)
    fn, specs = steps_mod.build_train_step(cfg, mesh, hp,
                                           global_batch=batch, seq_len=seq)
    info = mesh_info(mesh)
    params = prm.init_params(specs, jax.random.PRNGKey(0))
    opt = adamw.init_opt_state(params, specs, info)
    k = jax.random.PRNGKey(1)
    b = {"tokens": jax.random.randint(k, (batch, seq), 0, cfg.vocab_size,
                                      jnp.int32),
         "labels": jax.random.randint(k, (batch, seq), 0, cfg.vocab_size,
                                      jnp.int32)}
    step = jax.jit(fn)
    with jax.set_mesh(mesh):
        params, opt, m = step(params, opt, b)
        jax.block_until_ready(m["loss"])
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt, m = step(params, opt, b)
        jax.block_until_ready(m["loss"])
    return (time.perf_counter() - t0) / iters


def run_fig6():
    out = {}
    for cfg, seq, batch in GRID:
        key = f"{cfg.name}|s{seq}|b{batch}|tmp4|oases"
        out[key] = measure(cfg, seq, batch, 4, "oases", True)
        print(f"# {key}: {out[key]*1e3:.0f} ms", file=sys.stderr, flush=True)
    for tmp, schedule, fine in STRATS:
        key = (f"{BASE_CFG.name}|s256|b8|tmp{tmp}|{schedule}"
               + ("" if fine else "-coarse"))
        out[key] = measure(BASE_CFG, 256, 8, tmp, schedule, fine)
        print(f"# {key}: {out[key]*1e3:.0f} ms", file=sys.stderr, flush=True)
    return out


def run_measured(points: int = 0, iters: int = 3, telemetry: str = ""):
    """The measured tier: wall-clock AND calibrated-model tokens/s per
    (config x schedule) point.  ``points`` > 0 truncates the grid (the CI
    smoke runs exactly one point end-to-end).  ``telemetry``: directory
    for a structured JSONL trace of the run's per-point timings
    (uploaded as a CI artifact next to ``BENCH_<tag>.json``)."""
    from repro.core.planner import estimate_iteration
    from repro.core.planner.costmodel import HWConfig

    rec = None
    if telemetry:
        from repro import obs
        rec = obs.configure(telemetry)
    # calibrate FIRST (its ring mesh must not inherit a set_mesh scope)
    hw_fields = HWConfig.measure_fields(max_devices=8)
    hw = HWConfig(**hw_fields)
    todo = [(cfg, seq, batch, tmp, sched)
            for cfg, seq, batch, tmp in MEASURED_GRID
            for sched in MEASURED_SCHEDULES]
    if points > 0:
        todo = todo[:points]
    rows = []
    for cfg, seq, batch, tmp, sched in todo:
        fine = sched == "oases"
        key = f"{cfg.name}|s{seq}|b{batch}|tmp{tmp}|{sched}"
        t = measure(cfg, seq, batch, tmp, sched, fine, iters=iters)
        hp = TrainHParams(schedule=sched, fine_remat=fine, microbatch=1)
        shape = ShapeConfig("bench", seq, batch, "train")
        est = estimate_iteration(cfg, shape,
                                 hp, [tmp] * cfg.num_layers, hw,
                                 options=(2, 4, 8, 16))
        tokens = batch * seq
        rows.append({
            "key": key, "model": cfg.name, "seq": seq, "batch": batch,
            "tmp": tmp, "schedule": sched,
            "measured_s": t, "measured_tok_s": tokens / max(t, 1e-12),
            "modeled_s": est["iter_s"],
            "modeled_tok_s": est["tokens_per_s"],
        })
        if rec is not None:
            rec.observe("bench.measured_s", t, key=key)
            rec.event("bench.point", key=key,
                      measured_ms=round(t * 1e3, 2),
                      modeled_ms=round(est["iter_s"] * 1e3, 2))
        print(f"# {key}: measured {t*1e3:.0f} ms / modeled "
              f"{est['iter_s']*1e3:.0f} ms", file=sys.stderr, flush=True)
    if rec is not None:
        rec.close()
    return {"hw": hw_fields, "iters": iters, "points": rows}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--tier", choices=["fig6", "measured"], default="fig6")
    ap.add_argument("--points", type=int, default=0,
                    help="measured tier: truncate the grid to the first N "
                         "points (0 = full grid; the CI smoke uses 1)")
    ap.add_argument("--iters", type=int, default=3,
                    help="timed iterations per point (after one blocked "
                         "warm-up step)")
    ap.add_argument("--telemetry", default="",
                    help="measured tier: JSONL telemetry directory "
                         "(per-point timings)")
    args = ap.parse_args()
    if args.tier == "measured":
        print(json.dumps(run_measured(points=args.points,
                                      iters=args.iters,
                                      telemetry=args.telemetry)))
    else:
        print(json.dumps(run_fig6()))


if __name__ == "__main__":
    main()
