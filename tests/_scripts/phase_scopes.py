"""Subprocess body: on a 1x4 TMP mesh the compiled train step names the
phase of every collective — each all-reduce of the schedule carries its
``tmp.*`` scope in the forward and in the backward pass, and the update's
ops carry ``optim.adamw`` (read by ``bench/phases.py``)."""
import runner  # noqa: F401  (must be first: sets XLA_FLAGS before jax)

import sys
from pathlib import Path

import jax

from repro.configs.base import TrainHParams
from repro.launch import steps

sys.path.insert(0, str(Path(__file__).resolve().parents[2] / "bench"))
import flops  # noqa: E402
import phases  # noqa: E402

mesh = runner.mesh(1, 4)
cfg = runner.reduced_config("gpt-h1024")
for schedule in ("megatron", "oases"):
    hp = TrainHParams(schedule=schedule, microbatch=1)
    fn, _ = steps.build_train_step(cfg, mesh, hp, global_batch=4,
                                   seq_len=32)
    args = steps.train_abstract_inputs(cfg, mesh, hp, global_batch=4,
                                       seq_len=32)
    with jax.set_mesh(mesh):
        text = jax.jit(fn).lower(*args).compile().as_text()
    att = phases.attribute(text)
    comps = flops.parse_hlo(text)[0]
    ars = [att[n] for ops in comps.values() for n, kind, _, _ in ops
           if kind in ("all-reduce", "all-reduce-start")]
    # the schedule's own reductions: its sub-batch scope over tmp.reduce
    sched = {p: sorted({s for q, s in ars if q == p
                        and s.startswith(f"tmp.{schedule}.sub")
                        and s.endswith("tmp.reduce")})
             for p in ("fwd", "bwd")}
    runner.report(f"phase-scopes-{schedule}", all(sched.values()),
                  f"{len(ars)} all-reduces; {sched}")
    runner.report(f"phase-optim-{schedule}",
                  any(p == "opt" for p, _ in att.values()),
                  f"{sum(p == 'opt' for p, _ in att.values())} opt ops")
