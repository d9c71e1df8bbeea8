"""Subprocess body: fine-grained recomputation (§3.2) removes the recompute
collectives — count psums in the grad jaxpr."""
import runner  # noqa: F401  (must be first: sets XLA_FLAGS before jax)

import jax
import jax.numpy as jnp

from repro.configs.base import TrainHParams
from repro.models import lm
from repro.models import params as prm

mesh = runner.mesh(2, 4)
counts = {}
for fine in [False, True]:
    cfg = runner.reduced_config("internlm2-1.8b")
    hp = TrainHParams(schedule="oases", fine_remat=fine)
    fn, specs, _ = lm.build_train_loss(cfg, mesh, hp, global_batch=4,
                                       seq_len=64)
    p = prm.init_params(specs, jax.random.PRNGKey(0))
    b = {"tokens": jnp.zeros((4, 64), jnp.int32),
         "labels": jnp.zeros((4, 64), jnp.int32)}
    with jax.set_mesh(mesh):
        jx = jax.make_jaxpr(jax.grad(lambda p, b: fn(p, b)[0]))(p, b)
    counts[fine] = str(jx).count("psum")
runner.report("remat-collectives", counts[True] < counts[False],
              f"coarse={counts[False]} fine={counts[True]}")
