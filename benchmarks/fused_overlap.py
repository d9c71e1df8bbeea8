"""Microbenchmark: the blocked-AllReduce path under each overlap backend.

A stack of row-parallel matmul "layers" (the exact shape every TMP block
exit takes: ``x @ W`` followed by the completing collective) is timed
forward+backward under every schedule, on 8 virtual CPU devices:

* ``megatron`` — blocking AllReduce after each layer matmul,
* ``wang``     — chunked matmul + chunked AllReduce (intra-op pipelining),
* ``oases``    — two sub-batches, program-order overlap window,
* ``fused``    — ring collective-matmul kernels (guaranteed per-step
                 overlap; :mod:`repro.kernels.collective_matmul`).

On a shared-core CPU host the wall clock mostly measures op-dispatch, so
alongside measured times the script prints the planner cost model's
prediction for the same four schedules on paper hardware — the quantity
the Oases ILP actually optimizes (the overlapped ``max(T_comm, T_compute)``
term for ``fused``).

Run: ``PYTHONPATH=src python benchmarks/fused_overlap.py``
"""
import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse
import time

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import compat
from repro.core.axes import mesh_info
from repro.core.schedule import SCHEDULES, TmpCtx, effective_split

BENCH_SCHEDULES = [s for s in SCHEDULES if s != "merak"]  # merak == oases here


def build_step(mesh, schedule, *, layers, batch, seq, d_model, d_ff):
    """Forward+backward through `layers` row-parallel matmul layers — the
    blocked-AllReduce path of Fig. 2/3 isolated from everything else."""
    info = mesh_info(mesh)
    ctx = TmpCtx(info, schedule=schedule)

    def body(ws, x):
        split = effective_split(schedule, 2, x.shape[0])
        subs = [x[i * (x.shape[0] // split):(i + 1) * (x.shape[0] // split)]
                for i in range(split)]
        total = jnp.float32(0.0)
        for w_up, w_down in zip(*ws):
            outs = []
            for s in subs:
                h = jnp.dot(s, w_up)            # column-parallel up
                outs.append(ctx.row_matmul(h, w_down))   # row-parallel + AR
            subs = [jnp.tanh(o) for o in outs]
        for s in subs:
            total = total + jnp.sum(s)
        return total

    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=((P(None, None, ("model",)), P(None, ("model",), None)),
                  P(("data",), None, None)),
        out_specs=P(), check_vma=False)

    def step(ws, x):
        return jax.value_and_grad(sm)(ws, x)

    k1, k2 = jax.random.split(jax.random.PRNGKey(0))
    ws = (0.02 * jax.random.normal(k1, (layers, d_model, d_ff)),
          0.02 * jax.random.normal(k2, (layers, d_ff, d_model)))
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, d_model))
    return jax.jit(step), ws, x


def measure(fn, ws, x, iters=5):
    out = fn(ws, x)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(ws, x)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def model_prediction():
    """Planner cost-model step times for the same comparison on paper HW."""
    from repro.configs.base import SHAPES, TrainHParams
    from repro.configs.registry import get_config
    from repro.core.planner import estimate_iteration
    cfg = get_config("internlm2-1.8b")
    degrees = [8] * cfg.num_layers
    rows = {}
    for sched in BENCH_SCHEDULES:
        est = estimate_iteration(cfg, SHAPES["train_4k"],
                                 TrainHParams(schedule=sched), degrees)
        rows[sched] = est["iter_s"]
    return rows


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=6)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--d-model", type=int, default=256)
    ap.add_argument("--d-ff", type=int, default=512)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    mesh = compat.make_mesh((2, 4), ("data", "model"))
    print(f"mesh (2 data x 4 model), {args.layers} layers, "
          f"batch {args.batch} x seq {args.seq} x d {args.d_model} "
          f"(d_ff {args.d_ff})\n")
    print(f"{'schedule':<10} {'measured ms/step':>18}")
    base = None
    results = {}
    for sched in BENCH_SCHEDULES:
        fn, ws, x = build_step(mesh, sched, layers=args.layers,
                               batch=args.batch, seq=args.seq,
                               d_model=args.d_model, d_ff=args.d_ff)
        with jax.set_mesh(mesh):
            t = measure(fn, ws, x, args.iters)
        results[sched] = t
        base = base or t
        print(f"{sched:<10} {t * 1e3:>14.2f} ms   ({base / t:4.2f}x)")

    print("\ncost-model prediction (paper HW, internlm2-1.8b @ degree 8):")
    rows = model_prediction()
    base = rows[BENCH_SCHEDULES[0]]
    for sched, t in rows.items():
        print(f"{sched:<10} {t * 1e3:>14.1f} ms   ({base / t:4.2f}x)")

    # overlap headroom from the blocking step's own compiled HLO: the gap
    # between serial (compute + comm) and overlapped max(compute, comm)
    # roofline seconds is what kernel fusion can recover on paper HW
    from repro.core.planner import V5E
    from repro.launch import hlo_cost
    fn, ws, x = build_step(mesh, "megatron", layers=args.layers,
                           batch=args.batch, seq=args.seq,
                           d_model=args.d_model, d_ff=args.d_ff)
    with jax.set_mesh(mesh):
        txt = jax.jit(fn).lower(ws, x).compile().as_text()
    cost = hlo_cost.analyze(txt, default_group=4)
    rf = cost.roofline_seconds(peak_flops=V5E.peak_flops,
                               hbm_bw=V5E.hbm_bw, link_bw=V5E.link_bw,
                               mxu_eff=V5E.mxu_base_eff)
    print(f"\nHLO roofline of the blocking step (paper HW): "
          f"serial {rf['serial_s'] * 1e6:.1f} us vs overlapped "
          f"{rf['overlapped_s'] * 1e6:.1f} us "
          f"({rf['serial_s'] / max(rf['overlapped_s'], 1e-12):4.2f}x headroom)")


if __name__ == "__main__":
    main()
