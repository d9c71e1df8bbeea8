"""Fused collective-matmul kernels: TMP collectives streamed through the
matmul hot path (paper §3 taken to kernel granularity).

The repo's four schedules express comm/compute overlap as *program
structure* and rely on XLA's latency-hiding scheduler.  This module is the
next level down: the collective is decomposed into a ring whose per-step
transfer is data-dependent ONLY on the previous step's tile matmul, so the
overlap is guaranteed by construction rather than hoped for.  Three fusions:

* ``matmul → reduce-scatter``  (row-parallel exit, SP mode): each ring step
  matmuls one output chunk and forwards the partial sum to the right
  neighbour while the next chunk's matmul runs.
* ``matmul → all-reduce``      (row-parallel exit, Megatron mode): the ring
  reduce-scatter above, whose matmuls all hide in the scatter phase,
  followed by a ring all-gather of the reduced chunk (same total link bytes
  as an AllReduce).
* ``all-gather → matmul``      (column-parallel entry, SP mode): shards are
  consumed by the matmul as they arrive; supports a *list* of weights so
  one ring feeds all of a block's entry projections (wq/wk/wv or wg/wu).

Three execution backends, selected by :func:`backend`:

* ``ref``    — ``jnp.dot`` + ``lax.psum``/``psum_scatter``/``all_gather``:
  the numerics oracle, and the fallback for multi-axis (factored-mesh)
  groups or non-divisible shapes.
* ``ring``   — the decomposition written with ``lax.ppermute`` +
  ``jax.lax.dot``: runs on every platform (this is what CPU tests and the
  8-virtual-device equivalence subprocesses validate), and on TPU already
  guarantees per-step independence in the emitted HLO.
* ``pallas`` — a single Pallas kernel per device: tile matmuls on the MXU
  with the ring transfer as a double-buffered ``make_async_remote_copy``
  that overlaps the next tile's compute (TPU only).

Gradients follow the partial-cotangent convention of :mod:`repro.core.tmp`:
the SP pair (`fused_allgather_matmul`/`fused_matmul_reducescatter`) are
custom-VJPs whose backward is itself a fused ring (AG→matmul transposes to
matmul→RS and vice versa, so the backward pass overlaps too);
``fused_matmul_allreduce`` is deliberately left transparent to autodiff —
like ``reduce_from_tmp`` it must stay visible to the fine-remat policy,
and JAX's transpose of the ring is automatically the reversed (still
overlapped) ring.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tmp import Axes, axes_index, axes_size

# --------------------------------------------------------------------------
# backend selection
# --------------------------------------------------------------------------


def backend(axes: Axes, size_along_dim: int, *,
            use_pallas: bool = False) -> str:
    """Pick the execution backend for a fused op.

    Ring fusion needs a single mesh axis (``lax.ppermute`` ring) and a
    divisible chunk dim; everything else falls back to the reference
    (blocking-collective) path.  The fallback is always correct for the
    all-reduce and all-gather flavours; reduce-scatter semantics require
    the divisibility regardless of backend (``psum_scatter`` tiled), which
    ``_dispatch_rs`` checks explicitly.
    """
    if len(axes) != 1:       # no axes, or a multi-axis (factored) group
        return "ref"
    n = axes_size(axes)
    if n <= 1:
        return "ref"
    if size_along_dim % n != 0:
        return "ref"
    if use_pallas and jax.default_backend() == "tpu":
        return "pallas"
    return "ring"


def _ring_perm(n: int, reverse: bool = False):
    if reverse:
        return [(j, (j - 1) % n) for j in range(n)]
    return [(j, (j + 1) % n) for j in range(n)]


# --------------------------------------------------------------------------
# reference path (numerics oracle + fallback)
# --------------------------------------------------------------------------
def matmul_allreduce_ref(x, w, axes: Axes):
    y = jnp.dot(x, w)
    return lax.psum(y, axes) if axes else y


def matmul_reducescatter_ref(x, w, axes: Axes, scatter_dim: int):
    y = jnp.dot(x, w)
    return (lax.psum_scatter(y, axes, scatter_dimension=scatter_dim,
                             tiled=True) if axes else y)


def allgather_matmul_ref(x, ws: Sequence, axes: Axes, gather_dim: int):
    h = lax.all_gather(x, axes, axis=gather_dim, tiled=True) if axes else x
    return tuple(jnp.dot(h, w) for w in ws)


# --------------------------------------------------------------------------
# ring decomposition (platform-independent fused path)
# --------------------------------------------------------------------------
def ring_matmul_reducescatter(x, w, axes: Axes, scatter_dim: int):
    """Row-parallel ``x @ w`` fused with a ring reduce-scatter of the output
    along ``scatter_dim``.

    Chunk schedule: at step s device i computes its local contribution to
    output chunk ``(i - 1 - s) mod n`` and adds it to the partial sum
    arriving from the left; after n steps (n-1 hops) device i holds the
    fully reduced chunk i.  The step-s matmul is independent of the
    in-flight step-(s-1) transfer — the overlap window.
    """
    return _ring_rs_multi(((x, w),), axes, scatter_dim)


def _ring_rs_multi(pairs, axes: Axes, scatter_dim: int):
    """Ring reduce-scatter of ``sum_k x_k @ w_k`` — one ring carries the
    summed partials, so a multi-weight backward (dx of the fused AG-matmul)
    pays the link bytes once instead of once per weight."""
    axis = axes[0]
    n = axes_size(axes)
    idx = axes_index(axes)
    chunk = pairs[0][0].shape[scatter_dim] // n

    def contrib(s):
        c = (idx - 1 - s) % n
        out = None
        for xk, wk in pairs:
            xc = lax.dynamic_slice_in_dim(xk, c * chunk, chunk,
                                          axis=scatter_dim)
            t = jnp.dot(xc, wk)
            out = t if out is None else out + t
        return out

    accum = contrib(0)
    for s in range(1, n):
        arriving = lax.ppermute(accum, axis, _ring_perm(n))
        accum = arriving + contrib(s)   # dot is independent of the permute
    return accum


def ring_allgather(y_chunk, axes: Axes, dim: int):
    """Plain ring all-gather of a local chunk along ``dim`` (the cool-down
    phase of the fused all-reduce; no compute left to hide)."""
    axis = axes[0]
    n = axes_size(axes)
    idx = axes_index(axes)
    chunk = y_chunk.shape[dim]
    full = y_chunk.shape[:dim] + (chunk * n,) + y_chunk.shape[dim + 1:]
    out = jnp.zeros(full, y_chunk.dtype)
    cur = y_chunk
    for s in range(n):
        src = (idx - s) % n           # after s reverse hops we hold chunk src
        out = lax.dynamic_update_slice_in_dim(out, cur, src * chunk, axis=dim)
        if s < n - 1:
            cur = lax.ppermute(cur, axis, _ring_perm(n))
    return out


def ring_matmul_allreduce(x, w, axes: Axes, scatter_dim: int):
    """Fused ``matmul → all-reduce`` = overlapped ring reduce-scatter (all
    matmul flops hide in the scatter phase) + ring all-gather (same total
    link bytes as a plain ring AllReduce: 2K(n-1)/n)."""
    y_chunk = ring_matmul_reducescatter(x, w, axes, scatter_dim)
    return ring_allgather(y_chunk, axes, scatter_dim)


def ring_allgather_matmul(x, ws: Sequence, axes: Axes, gather_dim: int,
                          *, contract: Sequence = ()):
    """Column-parallel entry: gathered shards of ``x`` are consumed by the
    matmul(s) as they arrive.  One ring feeds every weight in ``ws``.

    At step s device i holds shard ``(i + s) mod n`` (received from the
    right neighbour) and immediately matmuls it into the output row block
    while the next shard is in flight.

    ``contract``: optional full-size tensors to contract against the
    SAME rotating shards — entry j accumulates
    ``einsum('...f,...r->fr', contract[j][chunk src], shard)``, i.e.
    ``contract[j].T @ AG(x)`` without a second gather.  This is how the
    fused backward produces dw on the dx ring: the bytes go around once.
    Returns ``outs`` alone, or ``(outs, contracted)`` when ``contract``
    is non-empty.
    """
    axis = axes[0]
    n = axes_size(axes)
    idx = axes_index(axes)
    chunk = x.shape[gather_dim]

    outs = []
    for w in ws:
        full = (x.shape[:gather_dim] + (chunk * n,)
                + x.shape[gather_dim + 1:-1] + (w.shape[-1],))
        outs.append(jnp.zeros(full, jnp.result_type(x.dtype, w.dtype)))
    contracted = [None] * len(contract)

    cur = x
    for s in range(n):
        nxt = (lax.ppermute(cur, axis, _ring_perm(n, reverse=True))
               if s < n - 1 else None)
        src = (idx + s) % n
        for k, w in enumerate(ws):
            outs[k] = lax.dynamic_update_slice_in_dim(
                outs[k], jnp.dot(cur, w), src * chunk, axis=gather_dim)
        for j, f in enumerate(contract):
            fc = lax.dynamic_slice_in_dim(f, src * chunk, chunk,
                                          axis=gather_dim)
            t = jnp.einsum("...f,...r->fr", fc, cur)
            contracted[j] = t if contracted[j] is None else contracted[j] + t
        cur = nxt
    if contract:
        return tuple(outs), tuple(contracted)
    return tuple(outs)


# --------------------------------------------------------------------------
# Pallas TPU kernels: the ring transfer as in-kernel double-buffered RDMA
# --------------------------------------------------------------------------
def _mm_tile_kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    """Tiled matmul microkernel shared by the ring kernels' compute step:
    grid (m_tiles, n_tiles, k_tiles), fp32 VMEM accumulator."""
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    acc_scr[...] += jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _done():
        o_ref[...] = acc_scr[...].astype(o_ref.dtype)


def pallas_tile_matmul(x, w, *, block_m: Optional[int] = None,
                       block_n: Optional[int] = None,
                       block_k: Optional[int] = None,
                       interpret: Optional[bool] = None):
    """2-D tiled matmul ``[m, k] @ [k, n]`` — the per-ring-step compute of
    the collective kernels, exposed standalone so CPU tests can validate
    the tiling/accumulation in interpret mode.

    Block sizes left as ``None`` resolve through the autotuner
    (:func:`repro.kernels.autotune.tuned_blocks`, cached per shape and
    platform); explicit arguments always win."""
    interpret = (jax.default_backend() != "tpu") if interpret is None \
        else interpret
    m, k = x.shape
    k2, nn = w.shape
    assert k == k2, (x.shape, w.shape)
    if block_m is None or block_n is None or block_k is None:
        from repro.kernels.autotune import tuned_blocks
        tm, tn, tk = tuned_blocks(m, k, nn, dtype=x.dtype)
        block_m = tm if block_m is None else block_m
        block_n = tn if block_n is None else block_n
        block_k = tk if block_k is None else block_k
    bm, bn, bk = min(block_m, m), min(block_n, nn), min(block_k, k)
    pad_m, pad_n, pad_k = (-m) % bm, (-nn) % bn, (-k) % bk
    if pad_m or pad_k:
        x = jnp.pad(x, ((0, pad_m), (0, pad_k)))
    if pad_k or pad_n:
        w = jnp.pad(w, ((0, pad_k), (0, pad_n)))
    mp, kp, np_ = m + pad_m, k + pad_k, nn + pad_n
    nk = kp // bk
    out = pl.pallas_call(
        functools.partial(_mm_tile_kernel, nk=nk),
        grid=(mp // bm, np_ // bn, nk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda mi, ni, ki: (mi, ki)),
            pl.BlockSpec((bk, bn), lambda mi, ni, ki: (ki, ni)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda mi, ni, ki: (mi, ni)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(x, w)
    if pad_m or pad_n:
        out = out[:m, :nn]
    return out


def _rs_ring_kernel(x_ref, w_ref, o_ref, accum, cbuf, send_sem,
                    recv_sem, ack_sem, *, n_dev: int, axis_name: str):
    """Fused matmul→reduce-scatter, one device's kernel body.

    grid = (n_dev,) sequential ('arbitrary'): step s computes the tile
    matmul for output chunk (i-1-s) mod n — the caller pre-rolls x chunks
    so step s reads static block s — accumulates the partial arriving from
    the left, and STARTS the forward to the right without waiting: the
    transfer completes under step s+1's matmul.  That deferred wait is the
    whole point of the kernel.

    Buffering/flow control (everything 2-slot, slot = s % 2):

    * ``accum[slot]``  — this step's partial sum; the slot is reused at
      s+2, by which time the s-send's local readout has been drained
      (``send_sem`` waited one step late, which does not block overlap —
      it only gates on the NIC having read the buffer, not on delivery).
    * ``cbuf[slot]``   — landing buffer on the receiver.  The receiver
      acks consumption (remote ``semaphore_signal`` to its LEFT) before
      the sender reuses the slot at s+2 — without the ack a fast sender
      two steps ahead could clobber an unconsumed partial.
    """
    s = pl.program_id(0)
    slot, prev = s % 2, (s - 1) % 2
    my_id = jax.lax.axis_index(axis_name)
    left = (my_id - 1) % n_dev
    right = (my_id + 1) % n_dev

    @pl.when(s == 0)
    def _barrier():
        # neighbours must have entered the kernel before any RDMA lands
        bsem = pltpu.get_barrier_semaphore()
        for nb in (left, right):
            pltpu.semaphore_signal(bsem, inc=1, device_id=nb,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bsem, 2)

    partial_sum = jax.lax.dot_general(
        x_ref[...].astype(jnp.float32), w_ref[...].astype(jnp.float32),
        (((1,), (0,)), ((), ())))

    @pl.when(s == 0)
    def _first():
        accum[0] = partial_sum

    @pl.when(s > 0)
    def _rest():
        pltpu.semaphore_wait(recv_sem[prev], 1)     # left's partial landed
        accum[slot] = cbuf[prev] + partial_sum
        # cbuf[prev] is free again: ack the sender (our left neighbour)
        pltpu.semaphore_signal(ack_sem[prev], inc=1, device_id=left,
                               device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(send_sem[prev], 1)     # drain our s-1 send

    @pl.when(s < n_dev - 1)
    def _forward():
        @pl.when(s >= 2)
        def _flow_control():
            # right neighbour must have consumed our s-2 payload from this
            # slot (its ack) before we overwrite it
            pltpu.semaphore_wait(ack_sem[slot], 1)

        rdma = pltpu.make_async_remote_copy(
            src_ref=accum.at[slot],
            dst_ref=cbuf.at[slot],
            send_sem=send_sem.at[slot],
            recv_sem=recv_sem.at[slot],
            device_id=(right,),
            device_id_type=pltpu.DeviceIdType.LOGICAL,
        )
        rdma.start()                # NO wait: overlaps step s+1's matmul

    @pl.when(s == n_dev - 1)
    def _finish():
        o_ref[...] = accum[slot].astype(o_ref.dtype)
        # Drain outstanding acks so every semaphore is zero at kernel
        # exit.  Ledger: n-1 sends each draw one ack; _flow_control
        # consumed one per step for s in [2, n-2] (n-3 of them), leaving
        # the acks of the last two sends (steps n-3 and n-2) — one for
        # n_dev == 2 — outstanding here.
        pltpu.semaphore_wait(ack_sem[(n_dev - 2) % 2], 1)
        if n_dev >= 3:
            pltpu.semaphore_wait(ack_sem[(n_dev - 3) % 2], 1)


def pallas_matmul_reducescatter(x, w, axes: Axes, scatter_dim: int):
    """TPU path of the fused matmul→reduce-scatter.

    The scatter dim is moved to the front and the n chunks are reordered
    locally (flip + roll by the device index) so the kernel's step-s block
    is a STATIC slice — the kernel then runs the ring with in-kernel RDMA
    and no dynamic VMEM indexing.
    """
    axis = axes[0]
    n = axes_size(axes)
    idx = axes_index(axes)
    d_out = w.shape[-1]
    k = x.shape[-1]
    xm = jnp.moveaxis(x, scatter_dim, 0)          # [S, ..., K]
    mid = xm.shape[1:-1]
    s_full = xm.shape[0]
    rows = s_full * math.prod(mid)
    x2 = xm.reshape(rows, k)
    chunk = rows // n
    # local chunk order for step s is (i-1-s) mod n == flip-then-roll-by-i
    x2 = x2.reshape(n, chunk, k)[::-1]
    x2 = jnp.roll(x2, idx, axis=0).reshape(rows, k)
    out = pl.pallas_call(
        functools.partial(_rs_ring_kernel, n_dev=n, axis_name=axis),
        grid=(n,),
        in_specs=[
            pl.BlockSpec((chunk, k), lambda s: (s, 0)),
            pl.BlockSpec((k, d_out), lambda s: (0, 0)),
        ],
        out_specs=pl.BlockSpec((chunk, d_out), lambda s: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((chunk, d_out), x.dtype),
        scratch_shapes=[
            pltpu.VMEM((2, chunk, d_out), jnp.float32),    # accum (2-slot)
            pltpu.VMEM((2, chunk, d_out), jnp.float32),    # ring double-buf
            pltpu.SemaphoreType.DMA((2,)),                 # send
            pltpu.SemaphoreType.DMA((2,)),                 # recv
            pltpu.SemaphoreType.REGULAR((2,)),             # consumption ack
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            collective_id=0),
    )(x2, w)
    out = out.reshape((s_full // n,) + mid + (d_out,))
    return jnp.moveaxis(out, 0, scatter_dim)


# --------------------------------------------------------------------------
# public fused ops (gradient-aware)
# --------------------------------------------------------------------------
def _dispatch_rs(x, w, axes: Axes, scatter_dim: int, use_pallas: bool):
    n = axes_size(axes)
    if n > 1 and x.shape[scatter_dim] % n != 0:
        # no backend can save this: tiled reduce-scatter semantics need an
        # even split (psum_scatter would raise deeper with a worse message)
        raise ValueError(
            f"matmul→reduce-scatter: scatter dim {scatter_dim} of size "
            f"{x.shape[scatter_dim]} is not divisible by the TMP group "
            f"size {n}")
    be = backend(axes, x.shape[scatter_dim], use_pallas=use_pallas)
    if be == "ref":
        return matmul_reducescatter_ref(x, w, axes, scatter_dim)
    if be == "pallas":
        return pallas_matmul_reducescatter(x, w, axes, scatter_dim)
    return ring_matmul_reducescatter(x, w, axes, scatter_dim)


def _dispatch_ag(x, ws, axes: Axes, gather_dim: int, use_pallas: bool):
    # the AG ring needs no divisibility check (every device holds an equal
    # shard by construction) — only a single-axis ring of size > 1
    if len(axes) != 1 or axes_size(axes) <= 1:
        return allgather_matmul_ref(x, ws, axes, gather_dim)
    # pallas AG-matmul rides the ring path until the dedicated kernel lands
    # on a TPU runway; the ring already guarantees per-step independence.
    return ring_allgather_matmul(x, ws, axes, gather_dim)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_matmul_reducescatter(x, w, axes: Axes, scatter_dim: int = 1,
                               use_pallas: bool = False):
    """Row-parallel ``x @ w`` + ring reduce-scatter along ``scatter_dim``
    (the SP-mode block exit).  Backward is the transposed fused ring:
    ``dx = AG(g)-matmul`` ring, ``dw`` accumulated per arriving shard —
    consistent with the partial-cotangent convention (``psum_scatter``
    transposes to ``all_gather``, cf. ``sp_reduce_scatter``)."""
    return _dispatch_rs(x, w, axes, scatter_dim, use_pallas)


def _rs_fwd(x, w, axes, scatter_dim, use_pallas):
    return _dispatch_rs(x, w, axes, scatter_dim, use_pallas), (x, w)


def _rs_bwd(axes, scatter_dim, use_pallas, res, g):
    x, w = res
    if not axes or axes_size(axes) == 1:
        return jnp.dot(g, w.T).astype(x.dtype), \
            jnp.einsum("...k,...d->kd", x, g).astype(w.dtype)
    if len(axes) != 1:
        # multi-axis (factored-mesh) fallback: blocking collectives
        g_full = lax.all_gather(g, axes, axis=scatter_dim, tiled=True)
        return jnp.dot(g_full, w.T).astype(x.dtype), \
            jnp.einsum("...k,...d->kd", x, g_full).astype(w.dtype)
    # ONE ring: as each g shard arrives it feeds both the dx matmul and
    # the dw contraction against x's matching chunk — the cotangent's
    # bytes go around once, both gradients overlap the transfer.  dw stays
    # a per-shard partial: the shard_map boundary psums parameter
    # cotangents over replicated axes (partial-cotangent convention).
    (dx,), (dw,) = ring_allgather_matmul(g, (w.T,), axes, scatter_dim,
                                         contract=(x,))
    return dx.astype(x.dtype), dw.astype(w.dtype)


fused_matmul_reducescatter.defvjp(_rs_fwd, _rs_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def fused_allgather_matmul(x, ws, axes: Axes, gather_dim: int = 1,
                           use_pallas: bool = False):
    """Column-parallel block entry (SP mode): ring all-gather of ``x``
    along ``gather_dim`` with each arriving shard immediately consumed by
    every matmul in ``ws``.  Returns one output per weight.

    Backward: ``dx`` is a fused matmul→reduce-scatter ring (the transpose
    of AG under the partial-cotangent convention, cf. ``sp_all_gather``);
    ``dw_k`` re-gathers ``x`` ring-wise (Megatron-SP style: the sharded
    input is the residual, halving saved activations vs caching the
    gathered tensor)."""
    return _dispatch_ag(x, tuple(ws), axes, gather_dim, use_pallas)


def _ag_fwd(x, ws, axes, gather_dim, use_pallas):
    return _dispatch_ag(x, tuple(ws), axes, gather_dim, use_pallas), (x, ws)


def _ag_bwd(axes, gather_dim, use_pallas, res, gs):
    x, ws = res
    if not axes or axes_size(axes) == 1:
        dx = sum(jnp.dot(g, w.T) for g, w in zip(gs, ws))
        dws = tuple(jnp.einsum("...k,...d->kd", x, g).astype(w.dtype)
                    for g, w in zip(gs, ws))
        return dx.astype(x.dtype), dws
    if len(axes) != 1 or gs[0].shape[gather_dim] % axes_size(axes) != 0:
        # fallback: blocking collectives
        dx = lax.psum_scatter(
            sum(jnp.dot(g, w.T) for g, w in zip(gs, ws)), axes,
            scatter_dimension=gather_dim, tiled=True)
        x_full = lax.all_gather(x, axes, axis=gather_dim, tiled=True)
        dws = tuple(jnp.einsum("...k,...d->kd", x_full, g).astype(w.dtype)
                    for g, w in zip(gs, ws))
        return dx.astype(x.dtype), dws
    # dx: ONE reduce-scatter ring carrying the summed per-chunk partials
    # sum_k g_k @ w_k^T (reduce-scatter is linear — k rings would move the
    # same bytes k times)
    dx = _ring_rs_multi(tuple((g, w.T) for g, w in zip(gs, ws)), axes,
                        gather_dim)
    # dw_k: re-gather x ring-wise, contracting each arriving shard with
    # every g_k chunk while the next shard is in flight (Megatron-SP
    # residual economy: the sharded input is the residual, and the
    # contraction hides the gather)
    _, dws = ring_allgather_matmul(x, (), axes, gather_dim, contract=gs)
    dws = tuple(dw.T.astype(w.dtype) for dw, w in zip(dws, ws))
    return dx.astype(x.dtype), dws


fused_allgather_matmul.defvjp(_ag_fwd, _ag_bwd)


def fused_matmul_allreduce(x, w, axes: Axes, *, scatter_dim: int = 1,
                           use_pallas: bool = False):
    """Row-parallel ``x @ w`` + AllReduce as an overlapped RS+AG ring.

    Deliberately NOT a custom_vjp (mirrors ``reduce_from_tmp``): the ring
    is plain linear jax, so the fine-remat ``save_only_these_names`` policy
    sees through it — with the output checkpoint-named by the caller the
    recompute replays no collective — and JAX's transpose of the ring is
    automatically the reversed, still-overlapped ring.
    """
    n = axes_size(axes)
    if n <= 1:
        return jnp.dot(x, w)
    be = backend(axes, x.shape[scatter_dim], use_pallas=use_pallas)
    if be == "ref":
        return matmul_allreduce_ref(x, w, axes)
    if be == "pallas":
        y_chunk = pallas_matmul_reducescatter(x, w, axes, scatter_dim)
        return ring_allgather(y_chunk, axes, scatter_dim)
    return ring_matmul_allreduce(x, w, axes, scatter_dim)
