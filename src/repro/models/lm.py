"""Whole-model assembly + jit-able step functions.

The entire forward (and loss) runs inside ONE ``shard_map`` over the
production mesh so every TMP collective is explicit (``jax.lax.psum`` via
:mod:`repro.core.tmp`) and the Oases schedule controls its placement —
faithful to the paper rather than GSPMD-inferred communication.

Gradients: parameters enter the body replicated over their non-sharded mesh
axes; ``copy_to_tmp(w, replicated_axes)`` makes the backward emit the
correct gradient AllReduce over exactly those axes (this is also where the
classic DP-gradient-overlap happens — the psum sits inside backward where
the latency-hiding scheduler can overlap it with remaining compute).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.configs.base import GLOBAL_ATTN, ArchConfig, TrainHParams
from repro.core import tmp as tmpc
from repro.core.axes import MeshInfo, batch_pspec, mesh_info
from repro.core.remat import maybe_checkpoint
from repro.core.schedule import (TmpCtx, apply_layer, effective_split,
                                 merge_tree, split_tree)
from repro.models import blocks as blk
from repro.models import params as prm


# --------------------------------------------------------------------------
def _positions(b, s, dtype=jnp.int32):
    return jnp.broadcast_to(jnp.arange(s, dtype=dtype)[None, :], (b, s))


def _sp_degraded(what: str, reasons: Sequence[str]):
    """Surface an intentional seq-parallel/seq-shard degradation instead
    of silently dropping the flag (PR 5 rejects unknown schedules at
    construction; numerics-preserving fallbacks warn + emit telemetry)."""
    import warnings
    from repro.obs.recorder import get_recorder
    msg = f"{what} degraded: {'; '.join(reasons)}"
    get_recorder().event("parallelism.degraded", msg, what=what)
    warnings.warn(msg, RuntimeWarning, stacklevel=3)


def _run_encoder(cfg, ctx, params, ctx_embed):
    import dataclasses
    if ctx.seq_parallel:
        # encoder activations are not sequence-sharded (the decoder's cross
        # attention needs the full encoded sequence on every shard) — an
        # intentional, numerics-preserving degradation, surfaced once at
        # trace time rather than silently dropped
        _sp_degraded("seq_parallel", [
            "encoder blocks run full-sequence (the decoder's cross "
            "attention needs the whole encoded sequence on every shard)"])
        ctx = dataclasses.replace(ctx, seq_parallel=False,
                                  seq_shard=1)
    enc = params["encoder"]
    x = ctx_embed + enc["pos_embed"][None, : ctx_embed.shape[1]].astype(
        ctx_embed.dtype)
    layer = blk.encoder_layer_fn(cfg, ctx)

    def body(carry, p):
        return layer(p, carry), None

    x, _ = lax.scan(body, x, enc["blocks"])
    return tmpc.rms_norm(x, enc["final_ln"], cfg.norm_eps)


def _stack_scan(cfg, ctx, hp, params, xs, auxs, *, train=True):
    """Scan over stacked pattern blocks + unrolled tail. xs: list of
    sub-batches. Returns (xs, aux_loss_sum)."""
    n, pat, tail = prm.stack_layout(cfg)
    parts = {k: blk.train_parts(cfg, ctx, k) for k in set(pat) | set(tail)}

    def block_body(carry, layer_params):
        xs_c, aux_c = carry
        for pos, kind in enumerate(pat):
            xs_c, a = apply_layer(parts[kind], layer_params[pos], xs_c, auxs,
                                  hp.schedule)
            aux_c = aux_c + a
        return (xs_c, aux_c), None

    body = block_body
    if train:
        body = maybe_checkpoint(block_body, remat=hp.remat,
                                fine=hp.fine_remat)
    carry = (xs, jnp.zeros((), jnp.float32))
    if n:
        carry, _ = lax.scan(body, carry, tuple(params["blocks"]))
    xs, aux = carry
    for i, kind in enumerate(tail):
        if train:
            def tail_body(carry, p, kind=kind):
                xs_c, a = apply_layer(parts[kind], p, carry[0], auxs,
                                      hp.schedule)
                return (xs_c, carry[1] + a), None
            tail_body = maybe_checkpoint(tail_body, remat=hp.remat,
                                         fine=hp.fine_remat)
            (xs, aux), _ = tail_body((xs, aux), params["tail"][i])
        else:
            xs, a = apply_layer(parts[kind], params["tail"][i], xs, auxs,
                                hp.schedule)
            aux = aux + a
    return xs, aux


# --------------------------------------------------------------------------
# pipeline-parallel forward (interleaved 1F1B over the 'pipe' mesh axis)
# --------------------------------------------------------------------------
def _pipeline_scan(cfg, ctx, info: MeshInfo, hp, params, x):
    """Run the layer stack as an SPMD pipeline (core/pipeline.py).

    ``x`` [b, s, d] is the embedded batch, replicated over ``pipe`` and
    batch-sharded over the data axes as usual.  It is cut into
    ``hp.microbatch`` microbatches that stream through the stages; each
    stage applies its layer chunk with the unchanged TMP machinery
    (``apply_layer`` + the schedule's sub-batch split), so stage-internal
    collectives overlap exactly as without PP.  Returns ``(x, aux)`` where
    ``x`` is valid on the last stage only (masked downstream)."""
    from repro.core import pipeline as pl

    pp = info.pp
    v = max(hp.virtual_stages, 1)
    n_micro = max(hp.microbatch, 1)
    b, s = x.shape[0], x.shape[1]
    if b % n_micro:
        raise ValueError(
            f"pipeline microbatch count {n_micro} must divide the "
            f"per-shard batch {b} (global batch / dp)")
    mb = b // n_micro
    _, pat, _ = prm.stack_layout(cfg)
    parts = {k: blk.train_parts(cfg, ctx, k) for k in set(pat)}
    positions = _positions(mb, s)

    def stage_fn(c, h):
        # this device's virtual-stage chunk c: leading dims [v, 1(pipe), per]
        chunk = tuple(jax.tree_util.tree_map(lambda t: t[c, 0], bl)
                      for bl in params["blocks"])
        split = effective_split(hp.schedule, hp.split, mb)
        hs = split_tree(h, split)
        auxs = [{"positions": positions[: mb // split]}
                for _ in range(split)]

        def body(carry, layer_params):
            hs_c, a_c = carry
            for pos, kind in enumerate(pat):
                hs_c, a = apply_layer(parts[kind], layer_params[pos], hs_c,
                                      auxs, hp.schedule)
                a_c = a_c + a
            return (hs_c, a_c), None

        body = maybe_checkpoint(body, remat=hp.remat, fine=hp.fine_remat)
        (hs, aux), _ = lax.scan(body, (hs, jnp.zeros((), jnp.float32)),
                                chunk)
        return merge_tree(hs) if len(hs) > 1 else hs[0], aux

    x_mb = x.reshape((n_micro, mb) + tuple(x.shape[1:]))
    out, aux = pl.pipeline_apply(stage_fn, x_mb,
                                 pipe_axis=info.pipe_axes[0], pp=pp,
                                 virtual_stages=v)
    # each layer accumulates its (mean-normalized) aux once per microbatch
    # here but once per pass in the non-PP paths — renormalize so the aux
    # term does not grow with the 1F1B microbatch count
    return out.reshape((b,) + tuple(x.shape[1:])), aux / n_micro


# --------------------------------------------------------------------------
# planner-mode (mixed per-layer TMP degrees on the factored mesh)
# --------------------------------------------------------------------------
def _grouped_scan(cfg, info, hp, params, x, degrees, schedules=None,
                  seqs=None):
    """Mixed-strategy forward (planner mode): consecutive layers sharing
    ``(degree, schedule)`` execute as one scan group, each under its own
    ``TmpCtx`` and sub-batch split.

    Mixed DEGREES need the factored mesh: activations are replicated over
    all t-axes in Megatron style; the *batch* dim is additionally sharded
    over the t-axes a low-degree group reuses for data parallelism.
    Degree transitions therefore reshard the batch: degree decrease = free
    local slice (``batch_split``), degree increase = AllGather — exactly
    the Eq. 4 edge costs the planner charges.  Mixed SCHEDULES at uniform
    (mesh-following, ``degree=None``) groups run on any mesh: the reshard
    degenerates to a no-op and only the split/overlap structure changes
    between groups — numerically exact either way."""
    cur_axes: tuple = ()

    def reshard(x, new_axes):
        # The batch chunk held under ``cur_axes`` is indexed by the
        # LINEARIZED axes_index over the whole tuple, so partial
        # gathers/splits (only the changed axes) interleave chunks and
        # permute the batch against the labels — gather everything, then
        # re-split over the new tuple.  (Pure splits/gathers from/to the
        # replicated state keep the cheap single-collective form.)
        nonlocal cur_axes
        if new_axes != cur_axes:
            if cur_axes:
                x = tmpc.sp_all_gather(x, cur_axes, 0)
            if new_axes:
                x = tmpc.batch_split(x, new_axes, 0)
            cur_axes = new_axes
        return x

    aux_total = jnp.zeros((), jnp.float32)
    for g_params, g in zip(params["groups"],
                           prm.plan_groups(cfg, degrees, schedules, seqs)):
        sched = g.schedule if schedules is not None else hp.schedule
        ctx = TmpCtx(info, degree=g.degree, schedule=sched,
                     use_pallas=hp.use_pallas, layout=hp.tmp_layout,
                     seq_parallel=g.seq > 1, seq_shard=g.seq)
        x = reshard(x, info.extra_dp_axes(g.degree))
        s_full = x.shape[1]
        if g.seq > 1:
            # ring group (DESIGN.md §12): activations enter seq-sharded
            # over the group's model axes and leave gathered — the seq
            # analogue of the batch reshard edges above
            if g.seq != ctx.tp_total:
                raise ValueError(
                    f"layer group seq={g.seq} must equal its model group "
                    f"size ({ctx.tp_total}) — the KV ring spans exactly "
                    f"the group the heads would have sharded over")
            if s_full % g.seq:
                raise ValueError(
                    f"seq_len {s_full} is not divisible by the group's "
                    f"seq={g.seq}")
            x = tmpc.batch_split(x, ctx.tp_axes, 1)
        parts = blk.train_parts(cfg, ctx, g.kind)
        b = x.shape[0]
        split = effective_split(sched, hp.split, b)
        xs = split_tree(x, split)
        auxs = [{"positions": _positions(b // split, s_full)}
                for _ in range(split)]

        def body(carry, p, parts=parts, auxs=auxs, sched=sched):
            xs_c, a_c = carry
            xs_c, a = apply_layer(parts, p, xs_c, auxs, sched)
            return (xs_c, a_c + a), None

        body = maybe_checkpoint(body, remat=hp.remat, fine=hp.fine_remat)
        (xs, aux_total), _ = lax.scan(body, (xs, aux_total), g_params)
        x = merge_tree(xs) if len(xs) > 1 else xs[0]
        if g.seq > 1:
            x = tmpc.sp_all_gather(x, ctx.tp_axes, 1)
    x = reshard(x, ())
    return x, aux_total


# --------------------------------------------------------------------------
# step builders
# --------------------------------------------------------------------------
def _normalize_strategy(cfg, hp, degrees, schedules, seqs=None):
    """One normalization of the per-layer strategy inputs:

    * uniform per-layer schedules collapse into ``hp.schedule`` (the
      stacked fast path) when no degrees are pinned;
    * mixed schedules with no pinned degrees promote to the grouped path
      with mesh-following ``degree=None`` groups;
    * per-layer ring-attention ``seqs`` collapse into ``hp.seq_shard``
      when uniform over the whole stack (else they ride the grouped
      path); a uniform ``hp.seq_shard`` over a grouped plan re-expands
      into per-layer seqs;
    * the grouped path always carries an explicit schedule list so the
      spec grouping (models/params.py) and the execution grouping
      (``_grouped_scan``) agree by construction.
    """
    import dataclasses
    if seqs is not None:
        seqs = list(seqs)
        if len(seqs) != cfg.num_layers:
            raise ValueError(
                f"per-layer seqs have {len(seqs)} entries for a "
                f"{cfg.num_layers}-layer model")
        if len(set(seqs)) == 1:
            hp = dataclasses.replace(hp, seq_shard=seqs[0])
            seqs = None
    if schedules is not None:
        schedules = list(schedules)
        if len(schedules) != cfg.num_layers:
            raise ValueError(
                f"per-layer schedules have {len(schedules)} entries for "
                f"a {cfg.num_layers}-layer model")
        if len(set(schedules)) == 1:
            hp = dataclasses.replace(hp, schedule=schedules[0])
            schedules = None
        elif degrees is None:
            degrees = [None] * cfg.num_layers
    if seqs is not None and degrees is None:
        # mixed per-layer seqs always run the grouped path
        degrees = [None] * cfg.num_layers
    if degrees is not None and schedules is None:
        schedules = [hp.schedule] * cfg.num_layers
    if degrees is not None and seqs is None and hp.seq_shard > 1:
        # a uniform seq_shard on a grouped plan becomes per-layer seqs so
        # the spec/execution grouping carries it
        seqs = [hp.seq_shard] * cfg.num_layers
        hp = dataclasses.replace(hp, seq_shard=1)
    return degrees, schedules, seqs, hp



def build_train_loss(cfg: ArchConfig, mesh, hp: TrainHParams, *,
                     global_batch: int, seq_len: int,
                     degrees: Optional[Sequence[int]] = None,
                     schedules: Optional[Sequence[str]] = None,
                     seqs: Optional[Sequence[int]] = None):
    """Returns (loss_fn(params, batch) -> (loss, aux), specs, in_specs).

    ``degrees``/``schedules`` are the per-layer strategy of an executable
    :class:`~repro.core.plan.ParallelPlan`: mixed entries run through the
    grouped scan (consecutive layers sharing ``(degree, schedule)`` form
    one scan group); uniform plans keep the classic stacked layout.  A
    per-layer SCHEDULE list with uniform degrees runs on any mesh (the
    groups all follow the mesh model group); mixed DEGREES need the
    factored mesh as before."""
    info = mesh_info(mesh)
    degrees, schedules, seqs, hp = _normalize_strategy(cfg, hp, degrees,
                                                       schedules, seqs)
    # SP composes with the 1D layout only: in 2D the block entries/exits
    # are already per-axis collectives, not the SP AG/RS pair.  Under PP
    # the stage boundary ships the full-sequence activation, so SP is off.
    base_ctx = TmpCtx(info, layout=hp.tmp_layout)
    twod = base_ctx.is_2d
    blockers = []
    if info.tp <= 1:
        blockers.append("the mesh has no model axes (tp=1)")
    if degrees is not None:
        blockers.append("per-layer strategies run the grouped path "
                        "(groups shard their own sequences)")
    if seq_len % max(info.tp, 1):
        blockers.append(f"seq_len {seq_len} is not divisible by the "
                        f"model group size {info.tp}")
    if twod:
        blockers.append("the 2D layout's block entries/exits are "
                        "per-axis collectives, not the SP AG/RS pair")
    if info.pp > 1:
        blockers.append("pipeline stage boundaries ship full sequences")
    ring = hp.seq_shard > 1 and degrees is None
    if ring:
        # ring attention is a new, memory/layout-changing mode: an
        # unsatisfiable --seq-shard is a hard error, not a silent
        # fallback (satellite of ISSUE 9; cf. PR 5's schedule rejection)
        ring_blockers = list(blockers)
        if info.tp > 1 and hp.seq_shard != base_ctx.tp_total:
            ring_blockers.append(
                f"seq_shard {hp.seq_shard} != model group size "
                f"{base_ctx.tp_total} (the KV ring spans exactly the "
                f"group the heads would have sharded over)")
        if seq_len % hp.seq_shard:
            ring_blockers.append(
                f"seq_len {seq_len} is not divisible by seq_shard "
                f"{hp.seq_shard}")
        if ring_blockers:
            raise ValueError(
                "seq_shard (ring attention) cannot run here: "
                + "; ".join(ring_blockers))
    sp = bool((hp.seq_parallel or ring) and not blockers)
    if hp.seq_parallel and blockers and not ring:
        _sp_degraded("seq_parallel", blockers)
    specs = prm.model_specs(cfg, info, degrees=degrees, max_pos=seq_len,
                            layout=hp.tmp_layout,
                            virtual_stages=hp.virtual_stages,
                            schedules=schedules, seqs=seqs,
                            seq_shard=hp.seq_shard if ring else 1)
    ctx = TmpCtx(info, schedule=hp.schedule, use_pallas=hp.use_pallas,
                 seq_parallel=sp, seq_shard=hp.seq_shard if ring else 1,
                 layout=hp.tmp_layout)
    bspec = batch_pspec(info, global_batch)
    batch_specs = {"tokens": bspec, "labels": bspec}
    if cfg.context_len:
        batch_specs["ctx"] = bspec

    def body(params, batch):
        # NOTE: shard_map's transpose already emits the gradient AllReduce
        # over every axis a parameter's in_spec leaves replicated (incl. the
        # data axes — the classic DP gradient all-reduce, placed inside
        # backward where the latency-hiding scheduler overlaps it).
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        x = tmpc.vocab_parallel_embed(tokens, params["embed"], ctx.tp_axes,
                                      sp_seq_dim=1 if ctx.seq_parallel
                                      else None)
        if cfg.name.startswith("gemma") or cfg.name.startswith("recurrentgemma"):
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if "pos_embed" in params:
            pe = params["pos_embed"][None, :s].astype(x.dtype)
            x = x + (tmpc.batch_split(pe, ctx.tp_axes, 1)
                     if ctx.seq_parallel else pe)
        enc_out = None
        if cfg.is_encdec:
            enc_out = _run_encoder(cfg, ctx, params, batch["ctx"])
        elif cfg.context_len:
            enc_out = batch["ctx"]

        positions = _positions(b, s)
        if degrees is not None:
            x, aux = _grouped_scan(cfg, info, hp, params, x, degrees,
                                   schedules, seqs)
        elif info.pp > 1:
            x, aux = _pipeline_scan(cfg, ctx, info, hp, params, x)
        else:
            split = effective_split(hp.schedule, hp.split, b)
            xs = split_tree(x, split)
            auxs = []
            for j in range(split):
                a = {"positions": positions[:b // split]}
                if enc_out is not None:
                    a["ctx"] = split_tree(enc_out, split)[j]
                auxs.append(a)
            xs, aux = _stack_scan(cfg, ctx, hp, params, xs, auxs)
            x = merge_tree(xs) if len(xs) > 1 else xs[0]

        x = ctx.gather_seq(x)       # SP: reassemble for the LM-head loss
        x = tmpc.rms_norm(x, params["final_ln"], cfg.norm_eps)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"])
        loss_sum, count = tmpc.vocab_parallel_xent(
            x, head, labels, ctx.tp_axes, chunk=hp.loss_chunk,
            softcap=cfg.final_softcap)
        # aggregate over every batch-sharded axis; under PP only the last
        # stage holds real outputs — mask, then psum over pipe as well
        agg_axes = info.batch_axes
        if info.pp > 1:
            from repro.core import pipeline as pl
            loss_sum = pl.mask_to_last_stage(loss_sum, info.pipe_axes[0],
                                             info.pp)
            count = pl.mask_to_last_stage(count, info.pipe_axes[0], info.pp)
            agg_axes = pl.pipeline_batch_axes(info)
        loss_sum = tmpc.reduce_from_tmp(loss_sum, agg_axes)
        count = lax.psum(count, agg_axes) if agg_axes else count
        aux = tmpc.reduce_from_tmp(aux / max(cfg.num_layers, 1),
                                   agg_axes) / max(info.dp, 1)
        return loss_sum / count + aux, aux

    in_specs = (prm.pspec_tree(specs), batch_specs)
    sm = jax.shard_map(body, mesh=mesh,
                       in_specs=(in_specs[0],
                                 {k: v for k, v in batch_specs.items()}),
                       out_specs=(P(), P()), check_vma=False)
    return sm, specs, in_specs


def greedy_token(logits_local, tp_axes):
    """Vocab-parallel greedy sampling: [b, V_local] -> [b] global ids."""
    v_local = logits_local.shape[-1]
    off = tmpc.axes_index(tp_axes) * v_local
    val = jnp.max(logits_local, axis=-1)
    idx = jnp.argmax(logits_local, axis=-1).astype(jnp.int32) + off
    if not tp_axes:
        return idx
    vals = lax.all_gather(val, tp_axes)        # [tp, b]
    idxs = lax.all_gather(idx, tp_axes)
    win = jnp.argmax(vals, axis=0)
    return jnp.take_along_axis(idxs, win[None], axis=0)[0]


def _last_logits(cfg, params, x_last, ctx):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.dot(x_last.astype(jnp.float32), head.astype(jnp.float32))
    if cfg.final_softcap:
        logits = cfg.final_softcap * jnp.tanh(logits / cfg.final_softcap)
    return logits


def _decode_embed(cfg, ctx, params, tokens, pos):
    """Shared decode-step preamble: vocab-parallel embed of the current
    token + family scaling + clamped pos-embed gather (one source for the
    plain and pipeline decode bodies)."""
    x = tmpc.vocab_parallel_embed(tokens[:, None], params["embed"],
                                  ctx.tp_axes)
    if cfg.name.startswith("gemma") or cfg.name.startswith("recurrentgemma"):
        x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
    if "pos_embed" in params:
        pe = jnp.take(params["pos_embed"], jnp.minimum(
            pos, params["pos_embed"].shape[0] - 1), axis=0)
        x = x + pe[:, None].astype(x.dtype)
    return x


def _apply_cow(state, pat, tail, cow_src, cow_dst):
    """On-device copy-on-write for paged KV pools: copy page ``src`` over
    page ``dst`` in every GLOBAL_ATTN layer's k/v pool before the step
    writes.  ``cow_src``/``cow_dst`` are fixed-length int32 arrays padded
    with (0, 0) no-ops (page 0 is the reserved null page), so COW costs
    zero extra dispatches and the jitted step shape never changes.  The
    page axis is always -4 ([..., pages, page, kvh, hd]), which covers
    both the flat and the pipeline-restacked layouts."""
    def fix(entry):
        e = dict(entry)
        for key in ("k", "v"):
            leaf = e[key]
            taken = jnp.take(leaf, cow_src, axis=-4)
            idx = (Ellipsis, cow_dst) + (slice(None),) * 3
            e[key] = leaf.at[idx].set(taken)
        return e

    out = dict(state)
    out["blocks"] = [fix(ent) if pat[i] == GLOBAL_ATTN else ent
                     for i, ent in enumerate(state["blocks"])]
    out["tail"] = [fix(ent) if tail[i] == GLOBAL_ATTN else ent
                   for i, ent in enumerate(state.get("tail", []))]
    return out


def _no_pipe(info: MeshInfo, what: str):
    if info.pp > 1:
        raise ValueError(
            f"{what} does not support a 'pipe' mesh axis yet — decode "
            f"streams through pipeline stages (build_decode) but the "
            f"batched prefill path runs on a data x model mesh; drop the "
            f"pipe axis or admit prompts through decode steps (the "
            f"serving engine's default)")


def build_prefill(cfg: ArchConfig, mesh, hp: TrainHParams, *,
                  global_batch: int, seq_len: int):
    """prefill_step(params, batch) -> (next_token [b], state)."""
    info = mesh_info(mesh)
    _no_pipe(info, "prefill")
    specs = prm.model_specs(cfg, info, max_pos=seq_len + 1,
                            layout=hp.tmp_layout)
    ctx = TmpCtx(info, schedule=hp.schedule, use_pallas=hp.use_pallas,
                 layout=hp.tmp_layout)
    bspec = batch_pspec(info, global_batch)
    st_specs = prm.cache_specs(cfg, info, batch=global_batch, seq=seq_len,
                               batch_spec=bspec, layout=hp.tmp_layout)
    batch_specs = {"tokens": bspec}
    if cfg.context_len:
        batch_specs["ctx"] = bspec
    n, pat, tail = prm.stack_layout(cfg)

    def body(params, batch):
        tokens = batch["tokens"]
        b, s = tokens.shape
        x = tmpc.vocab_parallel_embed(tokens, params["embed"], ctx.tp_axes)
        if cfg.name.startswith("gemma") or cfg.name.startswith("recurrentgemma"):
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if "pos_embed" in params:
            x = x + params["pos_embed"][None, :s].astype(x.dtype)
        enc_out = None
        if cfg.is_encdec:
            enc_out = _run_encoder(cfg, ctx, params, batch["ctx"])
        elif cfg.context_len:
            enc_out = batch["ctx"]
        aux = {"positions": _positions(b, s), "ctx": enc_out}

        fns = {k: blk.prefill_fn(cfg, ctx, k) for k in set(pat) | set(tail)}
        sts: Dict[str, Any] = {"blocks": [], "tail": []}

        def block_body(x, layer_params):
            st_out = []
            for pos, kind in enumerate(pat):
                x, st = fns[kind](layer_params[pos], x, aux)
                st_out.append(st)
            return x, tuple(st_out)

        if n:
            x, stacked = lax.scan(block_body, x, tuple(params["blocks"]))
            sts["blocks"] = list(stacked)
        for i, kind in enumerate(tail):
            x, st = fns[kind](params["tail"][i], x, aux)
            sts["tail"].append(jax.tree_util.tree_map(lambda t: t[None], st))

        x = tmpc.rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = _last_logits(cfg, params, x[:, -1], ctx)
        return greedy_token(logits, ctx.tp_axes), sts

    st_out_specs = prm.pspec_tree(st_specs)
    sm = jax.shard_map(
        body, mesh=mesh, in_specs=(prm.pspec_tree(specs), batch_specs),
        out_specs=(bspec, st_out_specs), check_vma=False)
    return sm, specs, st_specs


def build_decode(cfg: ArchConfig, mesh, hp: TrainHParams, *,
                 global_batch: int, seq_len: int, n_micro: int = 0,
                 paged=None):
    """serve_step(params, state, tokens [b], pos [b]) -> (next [b], state).

    Decode runs under the same ``TmpCtx`` schedule machinery as training:
    ``hp.schedule == "fused"`` streams the projection all-reduces as rings
    chunked over the slot batch (the seq dim is 1 at decode — see
    ``TmpCtx._ring_dim``), so the collective transfers hide under the
    matmul tiles even at batch-1 shapes.  On a mesh with a ``pipe`` axis
    the layer stack is stage-sharded and the slot batch streams through the
    stages as ``n_micro`` micro-groups (``core/pipeline.decode_stream``):
    stage ``s`` decodes micro-group ``g`` while stage ``s-1`` decodes
    ``g+1``, with per-stage KV caches staying put on their stage.

    ``paged=(pages, page_size)`` switches GLOBAL_ATTN caches to the page
    pool layout and the step signature to
    ``(params, state, tokens, pos, tables, cow_src, cow_dst)`` — the
    engine passes each slot's block table every tick and schedules
    copy-on-write page copies through the padded cow arrays
    (:mod:`repro.serving.paged_cache`).  The slot batch runs replicated
    in paged mode (the pool is shared across slots, so data axes shard
    requests across engine replicas, not slots within a pool)."""
    info = mesh_info(mesh)
    specs = prm.model_specs(cfg, info, max_pos=seq_len + 8,
                            layout=hp.tmp_layout,
                            virtual_stages=hp.virtual_stages)
    ctx = TmpCtx(info, schedule=hp.schedule, use_pallas=hp.use_pallas,
                 layout=hp.tmp_layout)
    bspec = P() if paged is not None else batch_pspec(info, global_batch)
    st_specs = prm.cache_specs(cfg, info, batch=global_batch, seq=seq_len,
                               batch_spec=bspec, layout=hp.tmp_layout,
                               virtual_stages=hp.virtual_stages, paged=paged)
    n, pat, tail = prm.stack_layout(cfg)
    if info.pp > 1:
        return _build_decode_pp(cfg, mesh, hp, info, ctx, specs, st_specs,
                                bspec, global_batch, n_micro, paged=paged)

    def body(params, state, tokens, pos, *extra):
        aux = {"pos": pos}
        if paged is not None:
            tables, cow_src, cow_dst = extra
            state = _apply_cow(state, pat, tail, cow_src, cow_dst)
            aux["tables"] = tables
        x = _decode_embed(cfg, ctx, params, tokens, pos)
        fns = {k: blk.decode_fn(cfg, ctx, k) for k in set(pat) | set(tail)}

        # KV caches ride in the scan CARRY and are updated with in-place
        # dynamic_update_slice at the layer index — XLA aliases the (donated)
        # input cache straight through the loop, so decode temp memory stays
        # O(one layer), not O(2x full cache).
        def block_body(carry, inp):
            x, st_stack = carry
            layer_params, i = inp
            st_out = []
            for p_, kind in enumerate(pat):
                st_i = jax.tree_util.tree_map(
                    lambda t: lax.dynamic_index_in_dim(t, i, 0, False),
                    st_stack[p_])
                x, st = fns[kind](layer_params[p_], x, st_i, aux)
                st_out.append(st)
            st_stack = tuple(
                jax.tree_util.tree_map(
                    lambda t, s: lax.dynamic_update_index_in_dim(
                        t, s.astype(t.dtype), i, 0), st_stack[p_], st_out[p_])
                for p_ in range(len(pat)))
            return (x, st_stack), None

        new_state: Dict[str, Any] = {"blocks": [], "tail": []}
        if n:
            (x, blocks_st), _ = lax.scan(
                block_body, (x, tuple(state["blocks"])),
                (tuple(params["blocks"]), jnp.arange(n, dtype=jnp.int32)))
            new_state["blocks"] = list(blocks_st)
        for i, kind in enumerate(tail):
            st_i = jax.tree_util.tree_map(lambda t: t[0], state["tail"][i])
            x, st = fns[kind](params["tail"][i], x, st_i, aux)
            new_state["tail"].append(
                jax.tree_util.tree_map(lambda t: t[None], st))

        x = tmpc.rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = _last_logits(cfg, params, x[:, 0], ctx)
        return greedy_token(logits, ctx.tp_axes), new_state

    st_ps = prm.pspec_tree(st_specs)
    extra_ps = (P(), P(), P()) if paged is not None else ()
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(prm.pspec_tree(specs), st_ps, bspec, bspec) + extra_ps,
        out_specs=(bspec, st_ps), check_vma=False)
    return sm, specs, st_specs


def _build_decode_pp(cfg, mesh, hp, info, ctx, specs, st_specs, bspec,
                     global_batch, n_micro, paged=None):
    """Pipeline-parallel serve_step: per-stage token micro-step streaming.

    Stage ``s = c*pp + d`` holds layers ``[s*n/S, (s+1)*n/S)`` of the
    ``[v, pp, per]``-stacked params AND their KV caches; only activations
    ride the ``pipe`` ppermute ring.  The final hidden state is valid on
    the last stage — masked and psum-broadcast over ``pipe`` so every
    device samples the identical greedy token (the engine reads one global
    array)."""
    from repro.core import pipeline as pl
    from repro.core.axes import local_batch
    n, pat, _tail = prm.stack_layout(cfg)
    v = max(hp.virtual_stages, 1)
    per = n // (info.pp * v)
    pipe_ax = info.pipe_axes[0]
    # paged mode runs the slot batch replicated (shared page pool), so the
    # stream sees the full batch on every data shard
    b_local = (global_batch if paged is not None
               else local_batch(info, global_batch))
    micro = pl.resolve_decode_micro(b_local, info.pp, v, n_micro)
    mb = b_local // micro

    def body(params, state, tokens, pos, *extra):
        b = tokens.shape[0]
        if paged is not None:
            tables, cow_src, cow_dst = extra
            state = _apply_cow(state, pat, [], cow_src, cow_dst)
        x = _decode_embed(cfg, ctx, params, tokens, pos)
        fns = {k: blk.decode_fn(cfg, ctx, k) for k in set(pat)}

        def stage_fn(c, h, st_c, mc):
            # this device's virtual-stage chunk c: leading dims [v, 1, per]
            chunk = tuple(jax.tree_util.tree_map(lambda t: t[c, 0], bl)
                          for bl in params["blocks"])
            aux = {"pos": lax.dynamic_slice_in_dim(pos, mc * mb, mb,
                                                   axis=0)}
            if paged is not None:
                aux["tables"] = lax.dynamic_slice_in_dim(tables, mc * mb,
                                                         mb, axis=0)

            def block_body(carry, inp):
                xc, st_stack = carry
                layer_params, j = inp
                st_out = []
                for p_, kind in enumerate(pat):
                    st_j = jax.tree_util.tree_map(
                        lambda t: lax.dynamic_index_in_dim(t, j, 0, False),
                        st_stack[p_])
                    xc, stn = fns[kind](layer_params[p_], xc, st_j, aux)
                    st_out.append(stn)
                st_stack = tuple(
                    jax.tree_util.tree_map(
                        lambda t, s: lax.dynamic_update_index_in_dim(
                            t, s.astype(t.dtype), j, 0),
                        st_stack[p_], st_out[p_])
                    for p_ in range(len(pat)))
                return (xc, st_stack), None

            (h, st_c), _ = lax.scan(
                block_body, (h, st_c),
                (chunk, jnp.arange(per, dtype=jnp.int32)))
            return h, st_c

        x_mb = x.reshape((micro, mb) + tuple(x.shape[1:]))
        outs, new_blocks = pl.decode_stream(
            stage_fn, x_mb, tuple(state["blocks"]), pipe_axis=pipe_ax,
            pp=info.pp, virtual_stages=v, paged=paged is not None)
        x = outs.reshape((b,) + tuple(x.shape[1:]))
        x = lax.psum(pl.mask_to_last_stage(x, pipe_ax, info.pp), pipe_ax)
        x = tmpc.rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = _last_logits(cfg, params, x[:, 0], ctx)
        return greedy_token(logits, ctx.tp_axes), {"blocks": list(new_blocks),
                                                   "tail": []}

    st_ps = prm.pspec_tree(st_specs)
    extra_ps = (P(), P(), P()) if paged is not None else ()
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(prm.pspec_tree(specs), st_ps, bspec, bspec) + extra_ps,
        out_specs=(bspec, st_ps), check_vma=False)
    return sm, specs, st_specs


def build_verify(cfg: ArchConfig, mesh, hp: TrainHParams, *,
                 global_batch: int, seq_len: int, paged=None):
    """verify_step(params, state, tokens [b, qn], pos [b])
    -> (choices [b, qn], state): the speculative-decoding target forward.

    One batched pass writes KV for all ``qn`` draft tokens (positions
    ``pos..pos+qn-1``), attends causally within the block and returns the
    target's greedy choice *after* each token — ``choices[:, j]`` is what
    undrafted decode would have emitted given ``tokens[:, :j+1]``, so the
    engine's longest-agreeing-run acceptance is token-identical to the
    oracle.  Collective latency is paid once per ``qn`` tokens instead of
    per token: the amortization :func:`costmodel.decode_step_time` models
    with ``spec_k``.

    With ``paged=(pages, page_size)`` the step takes the same
    ``(tables, cow_src, cow_dst)`` trailing args as paged
    :func:`build_decode`.  Requires an all-GLOBAL_ATTN layer pattern and
    no ``pipe`` mesh axis (drafting across stage boundaries would stall
    the decode stream it is meant to fill)."""
    info = mesh_info(mesh)
    if info.pp > 1:
        raise ValueError(
            "speculative verification does not support a 'pipe' mesh axis "
            "yet — serve spec-decode on a data x model (TMP/2D) mesh, or "
            "drop --draft/--spec-k on pipeline meshes")
    n, pat, tail = prm.stack_layout(cfg)
    other = sorted((set(pat) | set(tail)) - {GLOBAL_ATTN})
    if other:
        raise ValueError(
            f"speculative decoding requires an all-global-attention "
            f"layer pattern; {cfg.name} mixes in {other} (ring-buffer "
            f"and recurrent states cannot absorb multi-token jumps)")
    specs = prm.model_specs(cfg, info, max_pos=seq_len + 8,
                            layout=hp.tmp_layout)
    ctx = TmpCtx(info, schedule=hp.schedule, use_pallas=hp.use_pallas,
                 layout=hp.tmp_layout)
    bspec = P() if paged is not None else batch_pspec(info, global_batch)
    st_specs = prm.cache_specs(cfg, info, batch=global_batch, seq=seq_len,
                               batch_spec=bspec, layout=hp.tmp_layout,
                               paged=paged)

    def body(params, state, tokens, pos, *extra):
        b, qn = tokens.shape
        aux = {"pos": pos}
        if paged is not None:
            tables, cow_src, cow_dst = extra
            state = _apply_cow(state, pat, tail, cow_src, cow_dst)
            aux["tables"] = tables
        x = tmpc.vocab_parallel_embed(tokens, params["embed"], ctx.tp_axes)
        if cfg.name.startswith("gemma") or cfg.name.startswith(
                "recurrentgemma"):
            x = x * jnp.asarray(math.sqrt(cfg.d_model), x.dtype)
        if "pos_embed" in params:
            positions = pos[:, None] + jnp.arange(qn, dtype=jnp.int32)[None]
            pe = jnp.take(params["pos_embed"], jnp.minimum(
                positions, params["pos_embed"].shape[0] - 1), axis=0)
            x = x + pe.astype(x.dtype)
        fns = {k: blk.verify_fn(cfg, ctx, k) for k in set(pat) | set(tail)}

        def block_body(carry, inp):
            xc, st_stack = carry
            layer_params, i = inp
            st_out = []
            for p_, kind in enumerate(pat):
                st_i = jax.tree_util.tree_map(
                    lambda t: lax.dynamic_index_in_dim(t, i, 0, False),
                    st_stack[p_])
                xc, st = fns[kind](layer_params[p_], xc, st_i, aux)
                st_out.append(st)
            st_stack = tuple(
                jax.tree_util.tree_map(
                    lambda t, s: lax.dynamic_update_index_in_dim(
                        t, s.astype(t.dtype), i, 0), st_stack[p_], st_out[p_])
                for p_ in range(len(pat)))
            return (xc, st_stack), None

        new_state: Dict[str, Any] = {"blocks": [], "tail": []}
        if n:
            (x, blocks_st), _ = lax.scan(
                block_body, (x, tuple(state["blocks"])),
                (tuple(params["blocks"]), jnp.arange(n, dtype=jnp.int32)))
            new_state["blocks"] = list(blocks_st)
        for i, kind in enumerate(tail):
            st_i = jax.tree_util.tree_map(lambda t: t[0], state["tail"][i])
            x, st = fns[kind](params["tail"][i], x, st_i, aux)
            new_state["tail"].append(
                jax.tree_util.tree_map(lambda t: t[None], st))

        x = tmpc.rms_norm(x, params["final_ln"], cfg.norm_eps)
        logits = _last_logits(cfg, params, x.reshape(b * qn, -1), ctx)
        return greedy_token(logits, ctx.tp_axes).reshape(b, qn), new_state

    st_ps = prm.pspec_tree(st_specs)
    extra_ps = (P(), P(), P()) if paged is not None else ()
    sm = jax.shard_map(
        body, mesh=mesh,
        in_specs=(prm.pspec_tree(specs), st_ps, bspec, bspec) + extra_ps,
        out_specs=(bspec, st_ps), check_vma=False)
    return sm, specs, st_specs
