"""SPMD pipeline parallelism over a ``pipe`` mesh axis (interleaved 1F1B).

The layer stack is cut into ``S = pp * v`` stages: ``pp`` physical stages
(one per pipe-mesh coordinate) times ``v`` *virtual* stages per device
(Megatron-style interleaving; ``v = 1`` degenerates to the classic
GPipe/1F1B fill-drain loop).  Stage ``s`` runs on device ``s mod pp`` as
that device's chunk ``c = s // pp`` — the strided placement that shrinks
the pipeline bubble from ``(pp-1)/m`` to ``(pp-1)/(v*m)`` of the work.

Everything runs inside the model's single ``shard_map``:

* microbatches are injected at (device 0, chunk 0), flow stage-to-stage via
  a circular ``lax.ppermute`` (shift +1 with wrap), and are collected at
  (device pp-1, chunk v-1);
* on the wrap (device pp-1 -> device 0) the per-device chunk buffers roll
  ``c -> c+1``, so a tensor that finished chunk ``c`` on the last device
  continues as chunk ``c+1`` on device 0 — the circular schedule;
* each device's buffers hold at most one in-flight microbatch per chunk;
  slots outside the fill/drain window process zeros whose outputs are
  masked (never reach the loss), so their gradient contribution is exactly
  zero.

Because the forward is a plain traced loop, ``jax.grad`` transposes it into
the *reverse* pipeline automatically — ``ppermute`` transposes to the
inverted permutation — and the cross-pass interleaving of forward
microbatch ``j+1`` with backward microbatch ``j`` is admitted as program
structure, exactly like the TMP schedules (DESIGN.md §2): gradient
accumulation across microbatches is folded into the schedule rather than an
outer loop.  Stage-internal TMP collectives (all schedules, including the
fused collective-matmul rings) are untouched: they run over the model axes,
orthogonal to ``pipe``.

Parameter layout: stacked layer groups are stored ``[v, pp, n/S, ...]``
with only the ``pp`` dim sharded (over ``pipe``).  The row-major flatten of
``(c, d, j)`` is the canonical layer order — stage ``s = c*pp + d`` holds
layers ``[s*n/S, (s+1)*n/S)`` — so a pure reshape moves checkpoints between
PP and non-PP meshes (the elastic re-mesh path, ``checkpoint/store.py``).
"""
from __future__ import annotations

from typing import Callable, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.axes import MeshInfo


def validate_stage_layout(cfg, n_blocks: int, n_tail: int, pp: int,
                          virtual_stages: int = 1) -> int:
    """Check the layer stack divides into ``pp * v`` equal stages; returns
    the per-stage scan length.  Raises a friendly ValueError otherwise."""
    v = max(virtual_stages, 1)
    if pp < 1:
        raise ValueError(f"pipeline degree must be >= 1, got {pp}")
    if getattr(cfg, "is_encdec", False) or getattr(cfg, "context_len", 0):
        raise ValueError(
            f"pipeline parallelism does not support encoder-decoder / "
            f"cross-attention architectures yet ({cfg.name}): the encoder "
            f"and context stream are not stage-partitioned — drop the "
            f"'pipe' mesh axis for this model")
    if n_tail:
        raise ValueError(
            f"pipeline parallelism requires num_layers divisible by the "
            f"layer pattern (no tail layers); {cfg.name} has "
            f"{cfg.num_layers} layers over a {len(cfg.layer_pattern)}-kind "
            f"pattern leaving {n_tail} tail layer(s)")
    stages = pp * v
    if n_blocks % stages:
        raise ValueError(
            f"cannot cut {n_blocks} layer group(s) of {cfg.name} into "
            f"pp={pp} x v={v} = {stages} equal pipeline stages; pick pp/"
            f"virtual_stages dividing {n_blocks} or adjust num_layers")
    return n_blocks // stages


def _resolve_divisor(local_batch: int, cap: int, requested: int,
                     what: str) -> int:
    """Shared micro-count resolution: the requested value must divide the
    per-shard batch (raises otherwise); auto (0) takes the largest divisor
    up to ``cap``."""
    local = max(local_batch, 1)
    if requested:
        if requested < 1 or local % requested:
            raise ValueError(
                f"{what} {requested} must be a positive divisor of the "
                f"per-shard batch {local}")
        return requested
    n = min(local, max(cap, 1))
    while n > 1 and local % n:
        n -= 1
    return max(n, 1)


def resolve_microbatch(local_batch: int, pp: int, virtual_stages: int = 1,
                       requested: int = 0) -> int:
    """Pipeline microbatch count: the requested value (validated), else the
    largest divisor of the per-shard batch up to ``2 * pp * v`` — enough
    microbatches in flight to keep the bubble below ~1/(2v), without
    shrinking each microbatch past usefulness."""
    return _resolve_divisor(local_batch, 2 * pp * max(virtual_stages, 1),
                            requested, "pipeline microbatch count")


def bubble_fraction(pp: int, n_micro: int, virtual_stages: int = 1) -> float:
    """Idle fraction of the interleaved 1F1B schedule:
    (pp-1) / (pp-1 + v*m)."""
    if pp <= 1:
        return 0.0
    v = max(virtual_stages, 1)
    return (pp - 1) / (pp - 1 + v * max(n_micro, 1))


def mask_to_last_stage(val, pipe_axis: str, pp: int):
    """Zero ``val`` everywhere except the final pipeline stage (whose shard
    holds the real model output); combine with a psum over ``pipe``."""
    last = lax.axis_index(pipe_axis) == pp - 1
    return jnp.where(last, val, jnp.zeros_like(val))


def pipeline_apply(stage_fn: Callable, x_micro, *, pipe_axis: str, pp: int,
                   virtual_stages: int = 1) -> Tuple[jax.Array, jax.Array]:
    """Drive the circular interleaved pipeline schedule.

    ``stage_fn(c, x) -> (y, aux)`` runs this device's virtual-stage chunk
    ``c`` on microbatch tensor ``x``; ``aux`` is a scalar f32
    accumulator (auxiliary losses).  ``x_micro`` is ``[n_micro, mb, ...]``,
    identical on every pipe shard (batch-sharded over the data axes only).

    Returns ``(out [n_micro, mb, ...], aux)`` where ``out`` holds the
    fully-processed microbatches on the LAST stage's shards (other shards
    carry zeros-derived garbage — mask with :func:`mask_to_last_stage`
    before the loss) and ``aux`` holds this shard's stages' masked
    contributions (psum over ``pipe`` + batch axes to total).

    The time loop is a ``lax.scan`` over the tick index, so trace/compile
    size is constant in the microbatch count (only the ``v`` chunk calls
    unroll); differentiating the scan yields the reverse pipeline.
    """
    v = max(virtual_stages, 1)
    stages = pp * v
    n_micro = int(x_micro.shape[0])
    d_idx = lax.axis_index(pipe_axis)
    perm = [(i, (i + 1) % pp) for i in range(pp)]

    def tick(carry, t):
        buf, aux_total = carry
        # stage (0, chunk 0) ingests microbatch t during the fill window;
        # other devices keep their in-flight state
        inject = lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
        buf = buf.at[0].set(jnp.where((t < n_micro) & (d_idx == 0),
                                      inject, buf[0]))
        new_chunks = []
        for c in range(v):
            y, aux_c = stage_fn(c, buf[c])
            # slot (c, d) holds microbatch m = t - stage_index; outside the
            # window it processed zeros — drop its aux contribution
            m = t - (c * pp + d_idx)
            valid = (m >= 0) & (m < n_micro)
            aux_total = aux_total + jnp.where(valid, aux_c,
                                              jnp.zeros_like(aux_c))
            new_chunks.append(y)
        buf = jnp.stack(new_chunks)
        # (device pp-1, chunk v-1) finishes microbatch t-(S-1) this tick —
        # emit it before the shift (garbage during fill; sliced off below)
        out_t = buf[v - 1]
        # advance one stage: shift along the pipe ring; on the wrap into
        # device 0 the tensor moves to the next virtual chunk (the finished
        # chunk v-1 output was collected above; chunk 0 frees for injection)
        buf = lax.ppermute(buf, pipe_axis, perm)
        rolled = jnp.concatenate(
            [jnp.zeros_like(buf[:1]), buf[:-1]], axis=0) if v > 1 \
            else jnp.zeros_like(buf)
        buf = jnp.where(d_idx == 0, rolled, buf)
        return (buf, aux_total), out_t

    buf0 = jnp.zeros((v,) + tuple(x_micro.shape[1:]), x_micro.dtype)
    aux0 = jnp.zeros((), jnp.float32)
    (_, aux_total), ys = lax.scan(
        tick, (buf0, aux0), jnp.arange(n_micro + stages - 1,
                                       dtype=jnp.int32))
    return ys[stages - 1:], aux_total


def decode_stream(stage_fn: Callable, x_micro, state, *, pipe_axis: str,
                  pp: int, virtual_stages: int = 1, paged: bool = False
                  ) -> Tuple[jax.Array, object]:
    """Stream decode micro-steps through the pipeline stages.

    The serving analogue of :func:`pipeline_apply`: the slot batch of one
    decode step is cut into ``n_micro`` micro-groups that flow through the
    stages tick by tick, so stage ``s`` decodes micro-group ``g`` while
    stage ``s-1`` decodes micro-group ``g+1`` — every stage is busy in the
    steady state instead of waiting for the full stack to traverse.  Unlike
    training there is no backward pass and the per-stage KV caches are
    *stateful*: they stay put on their stage (only activations ride the
    ``ppermute`` ring) and are updated in place for the micro-group a slot
    currently holds.

    ``x_micro``  — ``[n_micro, mb, ...]`` micro-grouped token activations,
    identical on every pipe shard.
    ``state``    — pytree of per-stage caches, local leaves
    ``[v, 1(pipe), per_stage, batch, ...]`` (models/params.cache_specs
    pipeline stacking); the batch dim (axis 3) spans all micro-groups.
    ``stage_fn(c, h, st_c, m)`` — run this device's virtual chunk ``c`` on
    micro-group tensor ``h`` with its cache slice ``st_c`` (leaves
    ``[per_stage, mb, ...]``, batch rows of micro-group ``m``); returns
    ``(y, st_c_new)``.

    Out-of-window slots process zeros/stale buffers whose outputs are
    discarded and whose cache writes are masked off — the cache is only
    ever written by the tick that legitimately owns micro-group ``m`` at
    that stage, which is what keeps sharded decode token-identical to the
    single-device oracle.  Returns ``(out [n_micro, mb, ...], state)``
    where ``out`` is valid on the last stage's shards (combine with
    :func:`mask_to_last_stage` + a psum over ``pipe`` to broadcast).

    ``paged=True``: cache leaves are page *pools*
    ``[v, 1, per_stage, pages, ...]`` with no batch axis — every
    micro-group reads/writes the shared pool through its own block-table
    rows, so the stage gets the full pool and an out-of-window tick's
    pool update is discarded wholesale (its gather/scatter targeted live
    pages of the clipped micro-group).
    """
    v = max(virtual_stages, 1)
    stages = pp * v
    n_micro = int(x_micro.shape[0])
    mb = int(x_micro.shape[1])
    d_idx = lax.axis_index(pipe_axis)
    perm = [(i, (i + 1) % pp) for i in range(pp)]
    tmap = jax.tree_util.tree_map

    def slice_state(st, c, start):
        if paged:
            return tmap(lambda leaf: leaf[c, 0], st)
        return tmap(lambda leaf: lax.dynamic_slice_in_dim(
            leaf[c, 0], start, mb, axis=1), st)

    def write_state(st, c, start, new, valid):
        def upd(leaf, nl):
            cur = leaf[c, 0]
            if paged:
                return leaf.at[c, 0].set(
                    jnp.where(valid, nl.astype(leaf.dtype), cur))
            nxt = lax.dynamic_update_slice_in_dim(
                cur, nl.astype(leaf.dtype), start, axis=1)
            return leaf.at[c, 0].set(jnp.where(valid, nxt, cur))
        return tmap(upd, st, new)

    def tick(carry, t):
        buf, st = carry
        inject = lax.dynamic_index_in_dim(
            x_micro, jnp.minimum(t, n_micro - 1), 0, keepdims=False)
        buf = buf.at[0].set(jnp.where((t < n_micro) & (d_idx == 0),
                                      inject, buf[0]))
        new_chunks = []
        for c in range(v):
            m = t - (c * pp + d_idx)
            valid = (m >= 0) & (m < n_micro)
            mc = jnp.clip(m, 0, n_micro - 1)
            start = mc * mb
            y, st_new = stage_fn(c, buf[c], slice_state(st, c, start), mc)
            st = write_state(st, c, start, st_new, valid)
            new_chunks.append(y)
        buf = jnp.stack(new_chunks)
        out_t = buf[v - 1]
        buf = lax.ppermute(buf, pipe_axis, perm)
        rolled = jnp.concatenate(
            [jnp.zeros_like(buf[:1]), buf[:-1]], axis=0) if v > 1 \
            else jnp.zeros_like(buf)
        buf = jnp.where(d_idx == 0, rolled, buf)
        return (buf, st), out_t

    buf0 = jnp.zeros((v,) + tuple(x_micro.shape[1:]), x_micro.dtype)
    (_, state), ys = lax.scan(
        tick, (buf0, state), jnp.arange(n_micro + stages - 1,
                                        dtype=jnp.int32))
    return ys[stages - 1:], state


def resolve_decode_micro(local_batch: int, pp: int, virtual_stages: int = 1,
                         requested: int = 0) -> int:
    """Decode micro-group count: the requested value (validated), else the
    largest divisor of the slot batch up to ``pp * v`` — exactly enough
    in-flight micro-groups to fill the pipe.  More would re-stream each
    stage's (memory-bound) weights extra times per engine step; fewer
    leaves stages idle."""
    return _resolve_divisor(local_batch, pp * max(virtual_stages, 1),
                            requested, "decode micro-group count")


def pipeline_batch_axes(info: MeshInfo) -> Tuple[str, ...]:
    """Axes a pipeline-parallel loss must aggregate over: the batch axes
    plus ``pipe`` (each stage contributes its masked slice)."""
    return info.batch_axes + info.pipe_axes
