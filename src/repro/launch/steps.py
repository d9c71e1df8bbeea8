"""Jit-able step functions shared by the trainer, server, dry-run and
benchmarks: train_step (fwd+bwd+AdamW), prefill_step, serve_step."""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs.base import ArchConfig, ShapeConfig, TrainHParams
from repro.core.axes import batch_pspec, deg_total, mesh_info
from repro.models import lm
from repro.models import params as prm
from repro.obs import phase_scope
from repro.optim import adamw


def _min_degree(degrees, tp: int) -> int:
    """Smallest *total* degree in a plan (entries None | int | (dx, dy);
    None = mesh-following, i.e. the whole ``tp`` group)."""
    return min(deg_total(d) or tp for d in degrees)


def unpack_plan(cfg: ArchConfig, hp: TrainHParams, plan,
                degrees=None, schedules=None):
    """Project an executable ParallelPlan onto the (hp, degrees,
    schedules) triple the step builders consume.  Explicit degrees/
    schedules win over the plan's (callers that pass both are layering a
    manual override on top)."""
    if plan is not None:
        plan.validate_for(cfg)
        hp = plan.apply(hp)
        if degrees is None:
            degrees = plan.planned_degrees
        if schedules is None and plan.uniform_schedule is None:
            schedules = list(plan.schedules)
    return hp, degrees, schedules


def auto_microbatch(global_batch: int, dp: int, seq_len: int,
                    d_model: int, num_layers: int,
                    act_budget: float = 5e9, act_shard: int = 1) -> int:
    """Gradient-accumulation count sized so one microbatch's rematerialized
    activations (~3 [t,d] bf16 tensors per layer with the fine policy) fit
    the activation budget, floored at 1 sequence per chip."""
    local = max(global_batch // max(dp, 1), 1)
    token_budget = act_budget * act_shard / (3.0 * d_model * 2.0
                                             * max(num_layers, 1))
    seqs = max(1, min(local, int(token_budget // max(seq_len, 1))))
    n = max(1, local // seqs)
    while n > 1 and (local % n or global_batch % n):
        n -= 1
    return n    # 1 = no accumulation (resolved; 0 means "auto")


def resolve_hp(hp: TrainHParams, shape_kind: str, global_batch: int,
               dp: int, *, seq_len: int = 4096, d_model: int = 4096,
               num_layers: int = 32, tp: int = 1) -> TrainHParams:
    """Fill auto fields (microbatch=0 -> auto for training).  Sequence
    parallelism shards the remat residuals tp-ways, so the activation
    budget stretches by tp."""
    import dataclasses
    if shape_kind == "train" and hp.microbatch == 0:
        # ring attention (seq_shard) shards the residuals like SP does
        shard = tp if (hp.seq_parallel or hp.seq_shard > 1) else 1
        return dataclasses.replace(
            hp, microbatch=auto_microbatch(global_batch, dp, seq_len,
                                           d_model, num_layers,
                                           act_shard=shard))
    return hp


def resolve_for_mesh(cfg: ArchConfig, info, hp: TrainHParams,
                     global_batch: int, seq_len: int,
                     degrees=None) -> TrainHParams:
    """One resolution used by build_train_step, the abstract-input builder
    and the Trainer so they always agree on the microbatch semantics.

    On a pipeline mesh ``hp.microbatch`` becomes the 1F1B microbatch count
    (gradient accumulation is folded into the schedule — no outer loop);
    otherwise the classic gradient-accumulation auto-sizing applies."""
    import dataclasses
    from repro.core import pipeline as pl
    if info.pp > 1:
        if degrees is not None:
            raise ValueError(
                "per-layer planner degrees do not compose with pipeline "
                "parallelism yet — drop degrees= or the 'pipe' mesh axis")
        n_micro = pl.resolve_microbatch(
            max(global_batch // max(info.dp, 1), 1), info.pp,
            max(hp.virtual_stages, 1), hp.microbatch)
        return dataclasses.replace(hp, microbatch=n_micro,
                                   seq_parallel=False)
    dp_eff = info.dp * (info.tp // _min_degree(degrees, info.tp)) \
        if degrees else info.dp
    return resolve_hp(hp, "train", global_batch, dp_eff, seq_len=seq_len,
                      d_model=cfg.d_model, num_layers=cfg.num_layers,
                      tp=info.tp)


def build_train_step(cfg: ArchConfig, mesh, hp: TrainHParams, *,
                     global_batch: int, seq_len: int,
                     degrees: Optional[Sequence[int]] = None,
                     schedules: Optional[Sequence[str]] = None,
                     plan=None):
    """returns (train_step(params, opt_state, batch) ->
                (params, opt_state, metrics), specs).

    ``plan``: an executable :class:`repro.core.plan.ParallelPlan` —
    desugars into (hp overrides, per-layer degrees/schedules) via
    :func:`unpack_plan`; the legacy kwargs keep working unchanged."""
    info = mesh_info(mesh)
    hp, degrees, schedules = unpack_plan(cfg, hp, plan, degrees, schedules)
    hp = resolve_for_mesh(cfg, info, hp, global_batch, seq_len, degrees)
    # pipeline mode: the microbatch loop IS the 1F1B schedule, folded into
    # loss_fn — the step sees the full batch and a single value_and_grad
    pipelined = info.pp > 1
    micro_b = global_batch // hp.microbatch \
        if (hp.microbatch > 1 and not pipelined) else global_batch
    loss_fn, specs, _ = lm.build_train_loss(
        cfg, mesh, hp, global_batch=micro_b, seq_len=seq_len,
        degrees=degrees, schedules=schedules,
        seqs=plan.planned_seqs if plan is not None else None)
    ocfg = adamw.AdamWConfig(
        learning_rate=hp.learning_rate, weight_decay=hp.weight_decay,
        warmup_steps=hp.warmup_steps, total_steps=hp.total_steps,
        grad_clip=hp.grad_clip)

    # ZeRO-sharded gradient layout: the f32 grad (and its accumulator) is
    # sharded like the optimizer state, so GSPMD turns the backward's
    # data-axis psum into a reduce-scatter and the accumulator shrinks by
    # dp (§Perf: this is what lets 20B-scale train cells fit 16 GB HBM).
    g_specs = adamw.opt_state_specs(specs, info, zero1=hp.zero1)["m"]
    g_shardings = prm.shardings_tree(g_specs, mesh)

    def _constrain(g):
        # shard FIRST (in the grad dtype), cast to f32 after — the other
        # order materializes a full-size f32 copy per chip before GSPMD
        # gets to slice it
        return jax.tree_util.tree_map(
            lambda t, s: jax.lax.with_sharding_constraint(t, s)
            .astype(jnp.float32), g, g_shardings)

    def train_step(params, opt_state, batch):
        # JAX names the two passes in each op's metadata (``jvp(`` and
        # ``transpose(``); ``train.grad_accum`` names the gradient glue
        # around them, and the optimizer scopes its own update
        if hp.microbatch and hp.microbatch > 1 and not pipelined:
            # gradient accumulation: batch arrives pre-shaped
            # [n_micro, B/n, ...] with the batch dim sharded on axis 1, so
            # indexing axis 0 never reshards.
            n = hp.microbatch

            def micro(i, acc):
                g_acc, l_acc = acc
                mb = jax.tree_util.tree_map(
                    lambda t: jax.lax.dynamic_index_in_dim(
                        t, i, 0, keepdims=False), batch)
                (ls, _), g = jax.value_and_grad(loss_fn, has_aux=True)(params, mb)
                with phase_scope("train.grad_accum"):
                    return (jax.tree_util.tree_map(
                        jnp.add, g_acc, _constrain(g)), l_acc + ls)

            with phase_scope("train.grad_accum"):
                zero_g = jax.tree_util.tree_map(
                    lambda w, s: jax.lax.with_sharding_constraint(
                        jnp.zeros(w.shape, jnp.float32), s),
                    params, g_shardings)
            grads, loss = jax.lax.fori_loop(0, n, micro, (zero_g, 0.0))
            with phase_scope("train.grad_accum"):
                grads = jax.tree_util.tree_map(lambda g: g / n, grads)
                loss = loss / n
        else:
            (loss, _), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            with phase_scope("train.grad_accum"):
                grads = _constrain(grads)
        params, opt_state, gnorm = adamw.apply_updates(
            params, grads, opt_state, ocfg, compress=hp.grad_compress,
            zero_shardings=g_shardings,
            param_shardings=prm.shardings_tree(specs, mesh))
        return params, opt_state, {"loss": loss, "grad_norm": gnorm}

    return train_step, specs


def train_abstract_inputs(cfg: ArchConfig, mesh, hp: TrainHParams, *,
                          global_batch: int, seq_len: int,
                          degrees=None, schedules=None, plan=None):
    """ShapeDtypeStruct stand-ins for every train_step input (no alloc).
    With gradient accumulation the batch arrives pre-shaped
    [n_micro, B/n, ...], batch dim sharded on axis 1."""
    info = mesh_info(mesh)
    hp, degrees, schedules = unpack_plan(cfg, hp, plan, degrees, schedules)
    hp = resolve_for_mesh(cfg, info, hp, global_batch, seq_len, degrees)
    # the ONE strategy normalization build_train_loss itself runs, so the
    # abstract specs agree with the traced step (grouped promotion, ring
    # seq collapse/expansion) by construction
    seqs = plan.planned_seqs if plan is not None else None
    degrees, schedules, seqs, hp = lm._normalize_strategy(
        cfg, hp, degrees, schedules, seqs)
    ring = hp.seq_shard > 1 and degrees is None
    specs = prm.model_specs(cfg, info, degrees=degrees, max_pos=seq_len,
                            layout=hp.tmp_layout,
                            virtual_stages=hp.virtual_stages,
                            schedules=schedules, seqs=seqs,
                            seq_shard=hp.seq_shard if ring else 1)
    params = prm.abstract_params(specs, mesh)
    opt_state = adamw.abstract_opt_state(specs, info, mesh, zero1=hp.zero1)
    # pipeline meshes take the flat batch; 1F1B slices microbatches itself
    n = hp.microbatch if (hp.microbatch > 1 and info.pp == 1) else 1
    micro_b = global_batch // n
    bp = batch_pspec(info, micro_b)
    lead = (n,) if n > 1 else ()
    spec_entries = ((None,) if n > 1 else ()) + tuple(bp)
    bs = NamedSharding(mesh, jax.sharding.PartitionSpec(*spec_entries))

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(lead + shape, dtype, sharding=bs)

    batch = {
        "tokens": sds((micro_b, seq_len), jnp.int32),
        "labels": sds((micro_b, seq_len), jnp.int32),
    }
    if cfg.context_len:
        cd = cfg.context_dim or cfg.d_model
        batch["ctx"] = sds((micro_b, cfg.context_len, cd), jnp.bfloat16)
    return params, opt_state, batch


def build_prefill_step(cfg, mesh, hp, *, global_batch, seq_len):
    fn, specs, st_specs = lm.build_prefill(
        cfg, mesh, hp, global_batch=global_batch, seq_len=seq_len)
    return fn, specs, st_specs


def prefill_abstract_inputs(cfg, mesh, hp, *, global_batch, seq_len):
    info = mesh_info(mesh)
    specs = prm.model_specs(cfg, info, max_pos=seq_len + 1,
                            layout=hp.tmp_layout)
    params = prm.abstract_params(specs, mesh)
    bs = NamedSharding(mesh, batch_pspec(info, global_batch))
    batch = {"tokens": jax.ShapeDtypeStruct((global_batch, seq_len),
                                            jnp.int32, sharding=bs)}
    if cfg.context_len:
        cd = cfg.context_dim or cfg.d_model
        batch["ctx"] = jax.ShapeDtypeStruct(
            (global_batch, cfg.context_len, cd), jnp.bfloat16, sharding=bs)
    return params, batch


def build_serve_step(cfg, mesh, hp, *, global_batch, seq_len):
    fn, specs, st_specs = lm.build_decode(
        cfg, mesh, hp, global_batch=global_batch, seq_len=seq_len)
    return fn, specs, st_specs


def serve_abstract_inputs(cfg, mesh, hp, *, global_batch, seq_len):
    info = mesh_info(mesh)
    specs = prm.model_specs(cfg, info, max_pos=seq_len + 8,
                            layout=hp.tmp_layout,
                            virtual_stages=hp.virtual_stages)
    params = prm.abstract_params(specs, mesh)
    bspec = batch_pspec(info, global_batch)
    st_specs = prm.cache_specs(cfg, info, batch=global_batch, seq=seq_len,
                               batch_spec=bspec, layout=hp.tmp_layout,
                               virtual_stages=hp.virtual_stages)
    state = prm.abstract_params(st_specs, mesh)
    bs = NamedSharding(mesh, bspec)
    tokens = jax.ShapeDtypeStruct((global_batch,), jnp.int32, sharding=bs)
    pos = jax.ShapeDtypeStruct((global_batch,), jnp.int32, sharding=bs)
    return params, state, tokens, pos


def input_specs(cfg: ArchConfig, shape: ShapeConfig, mesh,
                hp: Optional[TrainHParams] = None, degrees=None,
                schedules=None, plan=None):
    """The dry-run contract: ShapeDtypeStruct stand-ins for the step that
    this (arch x shape) cell lowers."""
    hp = hp or TrainHParams()
    if shape.kind == "train":
        return train_abstract_inputs(cfg, mesh, hp,
                                     global_batch=shape.global_batch,
                                     seq_len=shape.seq_len, degrees=degrees,
                                     schedules=schedules, plan=plan)
    hp, degrees, schedules = unpack_plan(cfg, hp, plan, degrees, schedules)
    if shape.kind == "prefill":
        return prefill_abstract_inputs(cfg, mesh, hp,
                                       global_batch=shape.global_batch,
                                       seq_len=shape.seq_len)
    return serve_abstract_inputs(cfg, mesh, hp,
                                 global_batch=shape.global_batch,
                                 seq_len=shape.seq_len)


def step_fn_for(cfg, shape, mesh, hp: Optional[TrainHParams] = None,
                degrees=None, schedules=None, plan=None):
    hp = hp or TrainHParams()
    if shape.kind == "train":
        fn, _ = build_train_step(cfg, mesh, hp,
                                 global_batch=shape.global_batch,
                                 seq_len=shape.seq_len, degrees=degrees,
                                 schedules=schedules, plan=plan)
        return fn
    hp, degrees, schedules = unpack_plan(cfg, hp, plan, degrees, schedules)
    if shape.kind == "prefill":
        return build_prefill_step(cfg, mesh, hp,
                                  global_batch=shape.global_batch,
                                  seq_len=shape.seq_len)[0]
    return build_serve_step(cfg, mesh, hp, global_batch=shape.global_batch,
                            seq_len=shape.seq_len)[0]
