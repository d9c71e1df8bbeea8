"""AdamW with mixed precision, ZeRO-1 state sharding, global-norm clipping,
cosine LR schedule, and optional int8 gradient compression with error
feedback (beyond-paper distributed-optimization tricks).

Pure-JAX pytree implementation (no optax dependency).  The optimizer step is
meant to run OUTSIDE shard_map (plain jit); sharding of states is declared
via NamedShardings derived from the param spec tree.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.axes import MeshInfo
from repro.models import params as prm
from repro.obs import phase_scope


@dataclass(frozen=True)
class AdamWConfig:
    learning_rate: float = 3e-4
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    warmup_steps: int = 100
    total_steps: int = 10_000
    grad_clip: float = 1.0


def lr_at(cfg: AdamWConfig, step):
    """Linear warmup + cosine decay to 10%."""
    step = step.astype(jnp.float32) if hasattr(step, "astype") else float(step)
    warm = jnp.minimum(step / max(cfg.warmup_steps, 1), 1.0)
    prog = jnp.clip((step - cfg.warmup_steps)
                    / max(cfg.total_steps - cfg.warmup_steps, 1), 0.0, 1.0)
    cos = 0.55 + 0.45 * jnp.cos(jnp.pi * prog)
    return cfg.learning_rate * warm * cos


# --------------------------------------------------------------------------
# state specs (ZeRO-1: shard f32 master/m/v over the data axes too)
# --------------------------------------------------------------------------
def _zero1_pspec(spec: prm.Spec, info: MeshInfo, enable: bool) -> P:
    """Additionally shard the largest replicated dim over the batch axes."""
    entries = list(spec.pspec) + [None] * (len(spec.shape) - len(spec.pspec))
    if not enable or not info.batch_axes:
        return P(*entries)
    dp = info.dp
    for i, (e, dim) in enumerate(zip(entries, spec.shape)):
        if e is None and dim % dp == 0 and dim >= dp:
            entries[i] = info.batch_axes if len(info.batch_axes) > 1 \
                else info.batch_axes[0]
            break
    return P(*entries)


def opt_state_specs(param_specs, info: MeshInfo, *, zero1: bool = True):
    """Spec tree for (master, m, v) — all f32, ZeRO-1 sharded."""
    def one(s: prm.Spec):
        ps = _zero1_pspec(s, info, zero1)
        return prm.Spec(s.shape, ps, jnp.float32, s.scale)
    f32 = prm.tree_map_specs(one, param_specs)
    return {"master": f32, "m": f32, "v": f32,
            "step": prm.Spec((), P(), jnp.int32, 0.0),
            "err": None}  # error-feedback buffers added when compression on


def init_opt_state(params, param_specs, info: MeshInfo, *, zero1: bool = True):
    specs = opt_state_specs(param_specs, info, zero1=zero1)
    def zeros(tree):
        return prm.tree_map_specs(
            lambda s: jnp.zeros(s.shape, s.dtype), tree)

    return {
        "master": jax.tree_util.tree_map(
            lambda w: w.astype(jnp.float32), params),
        "m": zeros(specs["m"]),
        "v": zeros(specs["v"]),
        "step": jnp.zeros((), jnp.int32),
        "err": None,
    }


def abstract_opt_state(param_specs, info: MeshInfo, mesh, *,
                       zero1: bool = True):
    specs = opt_state_specs(param_specs, info, zero1=zero1)
    def mk(tree):
        return prm.tree_map_specs(
            lambda s: jax.ShapeDtypeStruct(
                s.shape, s.dtype, sharding=NamedSharding(mesh, s.pspec)), tree)
    return {"master": mk(specs["master"]), "m": mk(specs["m"]),
            "v": mk(specs["v"]),
            "step": jax.ShapeDtypeStruct((), jnp.int32,
                                         sharding=NamedSharding(mesh, P())),
            "err": None}


# --------------------------------------------------------------------------
def global_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree_util.tree_leaves(tree)))


def compress_int8(g, err):
    """Int8 stochastic-free quantization with error feedback."""
    gf = g.astype(jnp.float32) + (err if err is not None else 0.0)
    scale = jnp.maximum(jnp.max(jnp.abs(gf)), 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127)
    deq = q * scale
    return deq, gf - deq


def apply_updates(params, grads, opt_state, cfg: AdamWConfig, *,
                  compress: bool = False, zero_shardings=None,
                  param_shardings=None):
    """One AdamW step.  Returns (new_params, new_opt_state, grad_norm).

    ``zero_shardings``/``param_shardings``: NamedSharding trees.  When given,
    the f32 grads and the m/v/master update run in the ZeRO-sharded layout
    (per-chip 1/dp size) and the master->param cast happens BEFORE the
    gather back to the replicated param layout — without this, XLA
    materializes three full-size f32 state tensors per chip (§Perf).

    Every op of the update, the gradient norm and clipping included, is
    staged under the ``optim.adamw`` scope, so a profile can tell the
    optimizer's device time from the passes'."""
    with phase_scope("optim.adamw"):
        return _apply_updates(params, grads, opt_state, cfg,
                              compress=compress,
                              zero_shardings=zero_shardings,
                              param_shardings=param_shardings)


def _apply_updates(params, grads, opt_state, cfg: AdamWConfig, *,
                   compress: bool, zero_shardings, param_shardings):
    step = opt_state["step"] + 1
    lr = lr_at(cfg, step)

    def _c(tree, shardings):
        if shardings is None:
            return tree
        return jax.tree_util.tree_map(
            lambda t, s: jax.lax.with_sharding_constraint(t, s),
            tree, shardings)

    grads = _c(jax.tree_util.tree_map(
        lambda g: g.astype(jnp.float32), grads), zero_shardings)
    if compress:
        err = opt_state["err"] or jax.tree_util.tree_map(
            lambda g: jnp.zeros(g.shape, jnp.float32), grads)
        pairs = jax.tree_util.tree_map(compress_int8, grads, err)
        grads = jax.tree_util.tree_map(lambda p: p[0], pairs,
                                       is_leaf=lambda x: isinstance(x, tuple))
        new_err = jax.tree_util.tree_map(lambda p: p[1], pairs,
                                         is_leaf=lambda x: isinstance(x, tuple))
    else:
        new_err = opt_state["err"]

    gnorm = global_norm(grads)
    clip = jnp.minimum(1.0, cfg.grad_clip / (gnorm + 1e-6))
    b1, b2 = cfg.beta1, cfg.beta2
    bc1 = 1 - b1 ** step.astype(jnp.float32)
    bc2 = 1 - b2 ** step.astype(jnp.float32)

    def upd(w32, m, v, g):
        g = g.astype(jnp.float32) * clip
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        mh, vh = m / bc1, v / bc2
        neww = w32 - lr * (mh / (jnp.sqrt(vh) + cfg.eps)
                           + cfg.weight_decay * w32 * (w32.ndim > 1))
        return neww, m, v

    out = jax.tree_util.tree_map(upd, opt_state["master"], opt_state["m"],
                                 opt_state["v"], grads)
    master = _c(jax.tree_util.tree_map(
        lambda t: t[0], out, is_leaf=lambda x: isinstance(x, tuple)),
        zero_shardings)
    m = _c(jax.tree_util.tree_map(
        lambda t: t[1], out, is_leaf=lambda x: isinstance(x, tuple)),
        zero_shardings)
    v = _c(jax.tree_util.tree_map(
        lambda t: t[2], out, is_leaf=lambda x: isinstance(x, tuple)),
        zero_shardings)
    # cast to the param dtype BEFORE the ZeRO->replicated gather so the
    # all-gather moves bf16, not f32
    new_params = jax.tree_util.tree_map(
        lambda w32, w: w32.astype(w.dtype), master, params)
    new_params = _c(new_params, param_shardings)
    return new_params, {"master": master, "m": m, "v": v, "step": step,
                        "err": new_err}, gnorm
