"""Ring attention: sequence-parallel flash attention whose KV blocks
circulate the TMP ring (ROADMAP item 2; Liu et al.'s blockwise ring
transformers meeting the fused-collective machinery of
:mod:`repro.kernels.collective_matmul`).

Q stays sequence-local; (K, V) rotate around the ring one neighbour hop
per step, and each step folds the arriving KV block into an online-softmax
carry (exactly :func:`repro.models.attention.chunked_attention`'s update).
The per-step transfer depends only on the *previous* step's block, so the
KV hop overlaps the current block's QK^T/PV compute the same way
``collective_matmul`` overlaps matmul tiles.

Three execution backends, selected by :func:`backend`:

* ``ref``    — ``lax.all_gather`` the KV shards, then ``chunked_attention``:
  the numerics oracle and the fallback for multi-axis (factored-mesh)
  groups or a degenerate ring.
* ``ring``   — ``lax.ppermute`` rotation + per-block online softmax: runs
  on every platform (what the 8-virtual-device CI tier validates).
* ``pallas`` — a single TPU kernel per device with the KV hop as a
  double-buffered in-kernel ``make_async_remote_copy`` (same semaphore
  protocol as ``_rs_ring_kernel``); forward only — the backward runs the
  ppermute ring.

Causal masking across shards: absolute positions ride the ring next to the
KV block, and each step's update is wrapped in a ``lax.cond`` on a
block-level visibility test (min KV position vs max Q position, and the
sliding-window analogue), so a shard skips the QK^T/PV FLOPs of remote
blocks that are entirely in its future — preserving the ~2x causal FLOP
saving at ring granularity.  The mask itself is still applied elementwise
inside the update, so the skip is a pure FLOP optimization.

Gradients: a custom VJP runs a *second* ring.  dQ accumulates locally
(Q never moves); (dK, dV) buffers travel WITH the rotating KV shard and
arrive back at their home device after the n-th hop — n ppermutes
backward, mirroring the n-1 forward.  Cotangents follow the
partial-cotangent convention of :mod:`repro.core.tmp` (per-shard dK/dV;
the shard_map boundary psums replicated-parameter grads).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.tmp import Axes, axes_index, axes_size
from repro.kernels.collective_matmul import _ring_perm
from repro.models.attention import NEG_INF, chunked_attention


# --------------------------------------------------------------------------
# backend selection
# --------------------------------------------------------------------------
def backend(axes: Axes, *, use_pallas: bool = False) -> str:
    """Pick the execution backend for a sequence-sharded attention call.

    The ring needs a single mesh axis (``lax.ppermute``); factored-mesh
    multi-axis groups and degenerate rings fall back to the gather
    reference, which is always correct.
    """
    if len(axes) != 1:
        return "ref"
    if axes_size(axes) <= 1:
        return "ref"
    if use_pallas and jax.default_backend() == "tpu":
        return "pallas"
    return "ring"


# --------------------------------------------------------------------------
# shared block math (mirrors chunked_attention's scan step)
# --------------------------------------------------------------------------
def _valid_mask(qp, pb, causal: bool, window: Optional[int]):
    """qp [b, sq], pb [b, ck] absolute positions (-1 = padding) ->
    [b, 1, 1, sq, ck] bool."""
    pbb = pb[:, None, None, None, :]
    qpb = qp[:, None, None, :, None]
    valid = pbb >= 0
    if causal:
        valid &= pbb <= qpb
    if window is not None:
        valid &= pbb > qpb - window
    return valid


def _step_needed(qp, pb, causal: bool, window: Optional[int]):
    """Scalar block-visibility test: False iff NO (q, kv) pair in this
    ring step can attend — the ``lax.cond`` skip that keeps the causal
    FLOP saving.  Conservative (range-based), so it may admit a block the
    elementwise mask then zeroes; never the reverse."""
    big = jnp.int32(1 << 30)
    pb_min = jnp.min(jnp.where(pb >= 0, pb, big))
    needed = jnp.any(pb >= 0)
    if causal:
        needed = jnp.logical_and(needed, pb_min <= jnp.max(qp))
    if window is not None:
        needed = jnp.logical_and(needed, jnp.max(pb) > jnp.min(qp) - window)
    return needed


def _block_update(qs, kb, vb, qp, pb, acc, m, l, *, causal, window, softcap):
    """One online-softmax block: qs [b,sq,kvh,g,hd] f32 pre-scaled;
    kb/vb [b,ck,kvh,hd]; carry acc [b,kvh,g,sq,hd], m/l [b,kvh,g,sq]."""
    s = jnp.einsum("bqkgh,bckh->bkgqc", qs, kb.astype(jnp.float32))
    if softcap:
        s = softcap * jnp.tanh(s / softcap)
    s = jnp.where(_valid_mask(qp, pb, causal, window), s, NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + jnp.sum(p, axis=-1)
    pv = jnp.einsum("bkgqc,bckh->bkgqh", p, vb.astype(jnp.float32))
    return acc * corr[..., None] + pv, m_new, l_new


def _finalize(acc, m, l, b, sq, h, hd, dtype):
    l_safe = jnp.maximum(l, 1e-30)
    out = (acc / l_safe[..., None]).transpose(0, 3, 1, 2, 4)
    return out.reshape(b, sq, h, hd).astype(dtype), m + jnp.log(l_safe)


# --------------------------------------------------------------------------
# ppermute ring (every platform; the CI-validated path)
# --------------------------------------------------------------------------
def _ring_forward(q, k, v, qp, kvp, axes, causal, window, softcap, scale):
    """-> (out [b,sq,h,hd], lse [b,kvh,g,sq] f32)."""
    axis, n = axes[0], axes_size(axes)
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qs = (q.astype(jnp.float32) * scale).reshape(b, sq, kvh, g, hd)
    acc = jnp.zeros((b, kvh, g, sq, hd), jnp.float32)
    m = jnp.full((b, kvh, g, sq), NEG_INF, jnp.float32)
    l = jnp.zeros((b, kvh, g, sq), jnp.float32)
    perm = _ring_perm(n, reverse=True)
    cur = (k, v, kvp)           # step s holds shard (idx + s) % n
    for s in range(n):
        # start the hop BEFORE the block compute: the transfer depends only
        # on the previous step, so it completes under this block's FLOPs
        nxt = (tuple(lax.ppermute(t, axis, perm) for t in cur)
               if s < n - 1 else None)
        kb, vb, pb = cur
        acc, m, l = lax.cond(
            _step_needed(qp, pb, causal, window),
            lambda ops, kb=kb, vb=vb, pb=pb: _block_update(
                qs, kb, vb, qp, pb, *ops,
                causal=causal, window=window, softcap=softcap),
            lambda ops: ops,
            (acc, m, l))
        cur = nxt
    return _finalize(acc, m, l, b, sq, h, hd, q.dtype)


def _ring_backward(res, do, axes, causal, window, softcap, scale):
    """The reverse ring: dQ local, (dK, dV) travel with the KV shard and
    are home after n hops."""
    q, k, v, qp, kvp, out, lse = res
    axis, n = axes[0], axes_size(axes)
    b, sq, h, hd = q.shape
    kvh = k.shape[2]
    g = h // kvh
    qs = (q.astype(jnp.float32) * scale).reshape(b, sq, kvh, g, hd)
    dof = do.astype(jnp.float32).reshape(b, sq, kvh, g, hd)
    outf = out.astype(jnp.float32).reshape(b, sq, kvh, g, hd)
    # D_i = sum_j P_ij dP_ij = <do_i, o_i> — global, yet locally computable
    delta = jnp.einsum("bqkgh,bqkgh->bkgq", dof, outf)
    dq = jnp.zeros((b, sq, kvh, g, hd), jnp.float32)
    perm = _ring_perm(n, reverse=True)
    cur = (k, v, kvp,
           jnp.zeros(k.shape, jnp.float32), jnp.zeros(v.shape, jnp.float32))
    for s in range(n):
        kb, vb, pb, dkb, dvb = cur

        def blk(ops, kb=kb, vb=vb, pb=pb):
            dq_c, dk_c, dv_c = ops
            kf = kb.astype(jnp.float32)
            z = jnp.einsum("bqkgh,bckh->bkgqc", qs, kf)
            if softcap:
                zc = softcap * jnp.tanh(z / softcap)
                damp = 1.0 - jnp.square(zc / softcap)
            else:
                zc = z
            valid = _valid_mask(qp, pb, causal, window)
            p = jnp.where(valid, jnp.exp(zc - lse[..., None]), 0.0)
            dp = jnp.einsum("bqkgh,bckh->bkgqc", dof,
                            vb.astype(jnp.float32))
            ds = p * (dp - delta[..., None])
            if softcap:
                ds = ds * damp
            dq_blk = jnp.einsum("bkgqc,bckh->bqkgh", ds, kf) * scale
            dk_blk = jnp.einsum("bkgqc,bqkgh->bckh", ds, qs)  # qs has scale
            dv_blk = jnp.einsum("bkgqc,bqkgh->bckh", p, dof)
            return dq_c + dq_blk, dk_c + dk_blk, dv_c + dv_blk

        dq, dkb, dvb = lax.cond(
            _step_needed(qp, pb, causal, window), blk, lambda ops: ops,
            (dq, dkb, dvb))
        if s < n - 1:
            cur = tuple(lax.ppermute(t, axis, perm)
                        for t in (kb, vb, pb, dkb, dvb))
        else:
            # n-th hop carries only the finished (dK, dV) home
            dkb, dvb = (lax.ppermute(t, axis, perm) for t in (dkb, dvb))
    return (dq.reshape(b, sq, h, hd).astype(q.dtype),
            dkb.astype(k.dtype), dvb.astype(v.dtype))


# --------------------------------------------------------------------------
# Pallas TPU forward: in-kernel RDMA double-buffering
# --------------------------------------------------------------------------
def _ring_attn_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref,
                      kbuf, vbuf, acc, m_scr, l_scr,
                      ksend, krecv, vsend, vrecv, ack_sem, *,
                      n_dev: int, axis_name: str, causal: bool,
                      window: Optional[int], softcap: float):
    """grid = (n_dev,) sequential: step s folds KV shard (i+s) mod n into
    the online-softmax carry and STARTS the hop of the current buffer to
    the LEFT neighbour without waiting — the RDMA completes under step
    s+1's QK^T/PV.  Same 2-slot protocol as ``_rs_ring_kernel``: the
    payload passes through ``kbuf/vbuf[slot = s % 2]``, the receiver acks
    consumption to its RIGHT (the sender) before the sender reuses the
    landing slot, and every semaphore is zero at kernel exit
    (sends s∈[0,n-2]; acks emitted and consumed s∈[1,n-2]).

    Assumes contiguous sequence sharding (positions derived from the ring
    index); the ppermute path handles arbitrary positions.
    """
    s = pl.program_id(0)
    slot, prev = s % 2, (s - 1) % 2
    my_id = jax.lax.axis_index(axis_name)
    left = (my_id - 1) % n_dev
    right = (my_id + 1) % n_dev
    b, sk, kvh, hd = k_ref.shape
    sq = q_ref.shape[3]

    @pl.when(s == 0)
    def _start():
        # neighbours must have entered the kernel before any RDMA lands
        bsem = pltpu.get_barrier_semaphore()
        for nb in (left, right):
            pltpu.semaphore_signal(bsem, inc=1, device_id=nb,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)
        pltpu.semaphore_wait(bsem, 2)
        kbuf[0] = k_ref[...]
        vbuf[0] = v_ref[...]
        acc[...] = jnp.zeros_like(acc)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    @pl.when(s > 0)
    def _landed():
        pltpu.semaphore_wait(krecv[slot], 1)    # this step's KV arrived
        pltpu.semaphore_wait(vrecv[slot], 1)
        pltpu.semaphore_wait(ksend[prev], 1)    # drain our s-1 sends
        pltpu.semaphore_wait(vsend[prev], 1)

        @pl.when(s <= n_dev - 2)
        def _ack():
            # kbuf/vbuf[prev] free: the right neighbour's step-s send
            # targets exactly that slot on us
            pltpu.semaphore_signal(ack_sem[prev], inc=1, device_id=right,
                                   device_id_type=pltpu.DeviceIdType.LOGICAL)

    @pl.when(s < n_dev - 1)
    def _hop():
        @pl.when(s >= 1)
        def _flow_control():
            # left must have consumed (and drained) the slot we target
            pltpu.semaphore_wait(ack_sem[(s + 1) % 2], 1)

        for buf, ssem, rsem in ((kbuf, ksend, krecv), (vbuf, vsend, vrecv)):
            pltpu.make_async_remote_copy(
                src_ref=buf.at[slot],
                dst_ref=buf.at[(s + 1) % 2],
                send_sem=ssem.at[slot],
                recv_sem=rsem.at[(s + 1) % 2],
                device_id=(left,),
                device_id_type=pltpu.DeviceIdType.LOGICAL,
            ).start()           # NO wait: overlaps this block's compute

    src = (my_id + s) % n_dev   # which KV shard sits in kbuf[slot]
    run = jnp.bool_(True)
    if causal:
        run = jnp.logical_and(run, src * sk <= my_id * sq + sq - 1)
    if window is not None:
        run = jnp.logical_and(run, src * sk + sk - 1 > my_id * sq - window)

    @pl.when(run)
    def _compute():
        kf = kbuf[slot].astype(jnp.float32)
        vf = vbuf[slot].astype(jnp.float32)
        sc = jnp.einsum("bkgqh,bckh->bkgqc", q_ref[...], kf)
        if softcap:
            sc = softcap * jnp.tanh(sc / softcap)
        qpos = my_id * sq + lax.broadcasted_iota(jnp.int32, (sq, sk), 0)
        kpos = src * sk + lax.broadcasted_iota(jnp.int32, (sq, sk), 1)
        valid = jnp.ones((sq, sk), jnp.bool_)
        if causal:
            valid = jnp.logical_and(valid, kpos <= qpos)
        if window is not None:
            valid = jnp.logical_and(valid, kpos > qpos - window)
        sc = jnp.where(valid[None, None, None], sc, NEG_INF)
        m_old = m_scr[...]
        m_new = jnp.maximum(m_old, jnp.max(sc, axis=-1))
        p = jnp.exp(sc - m_new[..., None])
        corr = jnp.exp(m_old - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1)
        acc[...] = (acc[...] * corr[..., None]
                    + jnp.einsum("bkgqc,bckh->bkgqh", p, vf))
        m_scr[...] = m_new

    @pl.when(s == n_dev - 1)
    def _finish():
        l_safe = jnp.maximum(l_scr[...], 1e-30)
        o_ref[...] = acc[...] / l_safe[..., None]
        lse_ref[...] = m_scr[...] + jnp.log(l_safe)


def pallas_ring_forward(q, k, v, axes: Axes, *, causal=True, window=None,
                        softcap=0.0, scale=None):
    """TPU forward of the KV ring; -> (out [b,sq,h,hd], lse [b,kvh,g,sq])."""
    axis, n = axes[0], axes_size(axes)
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    g = h // kvh
    scale = scale if scale is not None else hd ** -0.5
    qs = (q.astype(jnp.float32) * scale).reshape(
        b, sq, kvh, g, hd).transpose(0, 2, 3, 1, 4)       # [b,kvh,g,sq,hd]
    out, lse = pl.pallas_call(
        functools.partial(_ring_attn_kernel, n_dev=n, axis_name=axis,
                          causal=causal, window=window, softcap=softcap),
        grid=(n,),
        in_specs=[
            pl.BlockSpec(qs.shape, lambda s: (0, 0, 0, 0, 0)),
            pl.BlockSpec(k.shape, lambda s: (0, 0, 0, 0)),
            pl.BlockSpec(v.shape, lambda s: (0, 0, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec(qs.shape, lambda s: (0, 0, 0, 0, 0)),
            pl.BlockSpec(qs.shape[:4], lambda s: (0, 0, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(qs.shape, jnp.float32),
            jax.ShapeDtypeStruct(qs.shape[:4], jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2,) + k.shape, k.dtype),       # KV ring double-buf
            pltpu.VMEM((2,) + v.shape, v.dtype),
            pltpu.VMEM(qs.shape, jnp.float32),         # acc
            pltpu.VMEM(qs.shape[:4], jnp.float32),     # m
            pltpu.VMEM(qs.shape[:4], jnp.float32),     # l
            pltpu.SemaphoreType.DMA((2,)),             # k send
            pltpu.SemaphoreType.DMA((2,)),             # k recv
            pltpu.SemaphoreType.DMA((2,)),             # v send
            pltpu.SemaphoreType.DMA((2,)),             # v recv
            pltpu.SemaphoreType.REGULAR((2,)),         # consumption ack
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            collective_id=1),   # distinct from the fused-matmul ring
    )(qs, k, v)
    o = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, hd).astype(q.dtype)
    return o, lse


# --------------------------------------------------------------------------
# custom VJP
# --------------------------------------------------------------------------
@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1, 2, 3, 4, 5))
def _ring_attention(axes, causal, window, softcap, scale, use_pallas,
                    q, k, v, q_positions, kv_positions):
    out, _ = _ra_fwd(axes, causal, window, softcap, scale, use_pallas,
                     q, k, v, q_positions, kv_positions)
    return out


def _ra_fwd(axes, causal, window, softcap, scale, use_pallas,
            q, k, v, q_positions, kv_positions):
    if use_pallas:
        out, lse = pallas_ring_forward(q, k, v, axes, causal=causal,
                                       window=window, softcap=softcap,
                                       scale=scale)
    else:
        out, lse = _ring_forward(q, k, v, q_positions, kv_positions, axes,
                                 causal, window, softcap, scale)
    return out, (q, k, v, q_positions, kv_positions, out, lse)


def _ra_bwd(axes, causal, window, softcap, scale, use_pallas, res, do):
    dq, dk, dv = _ring_backward(res, do, axes, causal, window, softcap,
                                scale)
    _, _, _, qp, kvp, _, _ = res
    return (dq, dk, dv,
            np.zeros(qp.shape, jax.dtypes.float0),
            np.zeros(kvp.shape, jax.dtypes.float0))


_ring_attention.defvjp(_ra_fwd, _ra_bwd)


# --------------------------------------------------------------------------
# public entry
# --------------------------------------------------------------------------
def ring_attention(q, k, v, *, axes: Axes, causal: bool = True,
                   window: Optional[int] = None, softcap: float = 0.0,
                   scale: Optional[float] = None, q_positions=None,
                   kv_positions=None, use_pallas: bool = False):
    """Sequence-sharded attention over the ring formed by ``axes``.

    q [b, sq_local, h, hd]; k, v [b, sk_local, kvh, hd]; positions are
    ABSOLUTE (defaulting to the contiguous shard of ``arange``); padding
    KV rows carry position -1.  Returns [b, sq_local, h, hd].
    """
    b, sq, h, hd = q.shape
    sk = k.shape[1]
    scale = float(scale if scale is not None else hd ** -0.5)
    if q_positions is None:
        q_positions = (axes_index(axes) * sq
                       + jnp.arange(sq, dtype=jnp.int32))[None, :]
    if kv_positions is None:
        kv_positions = (axes_index(axes) * sk
                        + jnp.arange(sk, dtype=jnp.int32))[None, :]
    if q_positions.ndim == 1:
        q_positions = q_positions[None, :]
    if kv_positions.ndim == 1:
        kv_positions = kv_positions[None, :]
    q_positions = jnp.broadcast_to(q_positions, (b, sq)).astype(jnp.int32)
    kv_positions = jnp.broadcast_to(kv_positions, (b, sk)).astype(jnp.int32)

    be = backend(axes, use_pallas=use_pallas)
    if be == "ref":
        kg, vg = k, v
        pg = kv_positions
        if axes:
            kg = lax.all_gather(k, axes, axis=1, tiled=True)
            vg = lax.all_gather(v, axes, axis=1, tiled=True)
            pg = lax.all_gather(kv_positions, axes, axis=1, tiled=True)
        return chunked_attention(q, kg, vg, causal=causal,
                                 window=window, softcap=softcap,
                                 q_positions=q_positions, kv_positions=pg,
                                 scale=scale)
    return _ring_attention(tuple(axes), causal, window, float(softcap),
                           scale, be == "pallas",
                           q, k, v, q_positions, kv_positions)
