"""Quickstart: build an Oases-scheduled TMP model, take train steps, decode.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax
import jax.numpy as jnp

from repro.configs.base import TrainHParams
from repro.configs.registry import get_config
from repro.core.axes import mesh_info
from repro.launch.mesh import make_smoke_mesh
from repro.launch import steps as steps_mod
from repro.models import lm
from repro.models import params as prm
from repro.optim import adamw

# 1. pick an assigned architecture and shrink it for the CPU demo
cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
mesh = make_smoke_mesh()
hp = TrainHParams(schedule="oases", fine_remat=True, learning_rate=3e-3,
                  warmup_steps=2, total_steps=30)

# 2. the train step = Oases-scheduled forward + chunked vocab-parallel loss
#    + AdamW (ZeRO-1) — all inside one shard_map over the mesh
step_fn, specs = steps_mod.build_train_step(cfg, mesh, hp, global_batch=4,
                                            seq_len=64)
params = prm.init_params(specs, jax.random.PRNGKey(0))
opt = adamw.init_opt_state(params, specs, mesh_info(mesh))

k = jax.random.PRNGKey(1)
batch = {"tokens": jax.random.randint(k, (4, 64), 0, cfg.vocab_size),
         "labels": jax.random.randint(k, (4, 64), 0, cfg.vocab_size)}
step = jax.jit(step_fn)
with jax.set_mesh(mesh):
    for i in range(10):
        params, opt, m = step(params, opt, batch)
        print(f"step {i}: loss {float(m['loss']):.4f}")

# 3. serve: prefill a prompt, decode a few tokens greedily
pf, _, _ = lm.build_prefill(cfg, mesh, hp, global_batch=4, seq_len=64)
df, _, _ = lm.build_decode(cfg, mesh, hp, global_batch=4, seq_len=64)
with jax.set_mesh(mesh):
    tok, state = jax.jit(pf)(params, {"tokens": batch["tokens"]})
    outs = [int(t) for t in tok]
    pos = jnp.full((4,), 63, jnp.int32)
    for _ in range(5):
        tok, state = jax.jit(df)(params, state, tok, pos)
        pos = pos + 1
print("decoded continuation of sequence 0:", outs[0],
      "->", int(tok[0]))
print("OK")
