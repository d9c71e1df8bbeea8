"""Subprocess body: TMP-sharded loss/grads must equal single-device values.
Prints PASS/FAIL lines consumed by tests/test_distributed.py."""
import runner  # noqa: F401  (must be first: sets XLA_FLAGS before jax)

import jax
import jax.numpy as jnp

from repro.configs.base import TrainHParams
from repro.models import lm
from repro.models import params as prm

ARCHS = ["internlm2-1.8b", "gemma2-9b", "recurrentgemma-9b",
         "moonshot-v1-16b-a3b", "granite-moe-3b-a800m", "whisper-small",
         "mamba2-130m"]

for arch in ARCHS:
    l1, g1 = runner.train_loss_and_grads(arch, runner.mesh(1, 1))
    l2, g2 = runner.train_loss_and_grads(arch, runner.mesh(2, 4))
    gerr = runner.grads_err(g1, g2)
    runner.report(arch, abs(l1 - l2) < 2e-4 and gerr < 5e-3,
                  f"dloss={abs(l1 - l2):.2e} gerr={gerr:.2e}")

# all four program-order schedules agree on the loss
losses = {}
mesh = runner.mesh(2, 4)
for sched in ["megatron", "wang", "merak", "oases"]:
    cfg = runner.reduced_config("internlm2-1.8b")
    hp = TrainHParams(schedule=sched)
    fn, specs, _ = lm.build_train_loss(cfg, mesh, hp, global_batch=4,
                                       seq_len=64)
    p = prm.init_params(specs, jax.random.PRNGKey(0))
    b = {"tokens": jnp.ones((4, 64), jnp.int32),
         "labels": jnp.ones((4, 64), jnp.int32)}
    with jax.set_mesh(mesh):
        losses[sched] = float(jax.jit(fn)(p, b)[0])
spread = max(losses.values()) - min(losses.values())
runner.report("schedules", spread < 1e-5, f"spread={spread:.2e}")
