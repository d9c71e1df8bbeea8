"""Compile each cell's step for a described TPU v5e, with no chip attached.

    JAX_PLATFORMS=cpu python bench/rehearse.py [cell ...]

For each cell: the compiled bytes per chip (``memory_analysis``), whether
they fit the chip, the collectives in the compiled module, and the matmul
work the module executes per step against the model FLOPs.  Runs on the
CPU; nothing here is a chip measurement.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

os.environ.setdefault("TPU_LOG_DIR", "disabled")
BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import flops as F  # noqa: E402
import harness as H  # noqa: E402


def described_mesh(cfg, topo):
    from repro.core import compat
    d, m = (int(x) for x in cfg["mesh"].split("x"))
    return compat.make_mesh((d, m), ("data", "model"),
                            devices=topo.devices[:d * m])


def plan_for(acfg, hp, mesh):
    from repro.core.plan import ParallelPlan
    from repro.launch.mesh import mesh_signature
    shape, axes = mesh_signature(mesh)
    plan = ParallelPlan.from_hparams(hp, acfg.num_layers, mesh_shape=shape,
                                     mesh_axes=axes)
    return plan, plan.apply(hp)


def compile_train(cfg, topo):
    from repro.configs.base import TrainHParams
    from repro.launch import steps
    o = cfg["optimizer"]
    hp = TrainHParams(schedule=cfg["schedule"],
                      learning_rate=o["learning_rate"],
                      warmup_steps=o["warmup_steps"],
                      total_steps=o["total_steps"],
                      microbatch=cfg["microbatch"])
    acfg = H.program_config(cfg)
    mesh = described_mesh(cfg, topo)
    plan, hp = plan_for(acfg, hp, mesh)
    fn, _ = steps.build_train_step(acfg, mesh, hp,
                                   global_batch=cfg["global_batch"],
                                   seq_len=cfg["seq_len"], plan=plan)
    args = steps.train_abstract_inputs(acfg, mesh, hp,
                                       global_batch=cfg["global_batch"],
                                       seq_len=cfg["seq_len"], plan=plan)
    return steps.jit_train_step(fn, mesh, donate_argnums=(0, 1)).lower(
        *args).compile()


def compile_serve(cfg, topo):
    import jax
    from repro.configs.base import TrainHParams
    from repro.launch import steps
    acfg = H.program_config(cfg)
    mesh = described_mesh(cfg, topo)
    _, hp = plan_for(acfg, TrainHParams(schedule=cfg["schedule"]), mesh)
    fn, _, _ = steps.build_serve_step(acfg, mesh, hp,
                                      global_batch=cfg["slots"],
                                      seq_len=cfg["max_seq"])
    args = steps.serve_abstract_inputs(acfg, mesh, hp,
                                       global_batch=cfg["slots"],
                                       seq_len=cfg["max_seq"])
    return jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()


def main(names):
    from jax.experimental import topologies
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = names or [w["name"] for w in H.benchmark()["workloads"]]
    gib = 1024 ** 3
    for name in names:
        cell = H.Cell(name)
        cfg = cell.config
        c = (compile_train if cfg["kind"] == "train" else compile_serve)(
            cfg, topo)
        ma = c.memory_analysis()
        total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                 - ma.alias_size_in_bytes + ma.temp_size_in_bytes)
        text = c.as_text()
        colls = sorted({w for w in ("all-reduce", "all-gather",
                                    "reduce-scatter", "collective-permute",
                                    "all-to-all") if w in text})
        print(f"{name}: args {ma.argument_size_in_bytes / gib:.3f} GiB, "
              f"temp {ma.temp_size_in_bytes / gib:.3f} GiB, total "
              f"{total / gib:.3f} GiB per chip "
              f"({'fits' if total < 15.6 * gib else 'DOES NOT FIT'} 16 GB); "
              f"collectives {colls}", flush=True)
        if cfg["kind"] == "train":
            dots = F.executed_dot_flops(text)
            model = H.count(cfg, "model_flops_per_token")(
                cfg["model"], cfg["seq_len"]) \
                * cfg["global_batch"] * cfg["seq_len"] / cell.chips
            print(f"  executed matmul {dots:.6g} FLOP per step per chip, "
                  f"model {model:.6g} ({dots / model:.4f}x)", flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
