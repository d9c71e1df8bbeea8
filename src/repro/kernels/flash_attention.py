"""Pallas TPU flash attention (the compute hot-spot every Oases TMP block
overlaps around).

Tiling: grid (batch, q_head, q_block, kv_block); the kv_block dimension is
sequential ('arbitrary') and carries the online-softmax state in VMEM
scratch — the accumulator never round-trips HBM (this is precisely the
traffic that dominates the memory roofline term of the pure-jnp ref path).
Causal/local blocks that are fully masked are skipped with ``pl.when`` —
on the MXU this realizes the ~2x causal FLOP saving the ref path cannot.

Supports GQA (kv head = q head // group), sliding-window (local) masking
and gemma2-style attention-logit softcap.  Validated against
:mod:`repro.kernels.ref` in interpret mode on CPU.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: Optional[int],
            softcap: float, bq: int, bk: int, seq_k: int):
    qi = pl.program_id(2)
    ki = pl.program_id(3)
    nk = pl.num_programs(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * bq
    k_start = ki * bk
    run = True
    if causal:
        run = jnp.logical_and(run, q_start + bq - 1 >= k_start)
    if window is not None:
        run = jnp.logical_and(run, k_start + bk - 1 > q_start - window)

    @pl.when(run)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [bq, hd]
        k = k_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0, 0].astype(jnp.float32)                  # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [bq, bk]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        q_pos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < seq_k
        if causal:
            mask = jnp.logical_and(mask, k_pos <= q_pos)
        if window is not None:
            mask = jnp.logical_and(mask, k_pos > q_pos - window)
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ()))))

    @pl.when(ki == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def flash_attention(q, k, v, *, causal: bool = True,
                    window: Optional[int] = None, softcap: float = 0.0,
                    scale: Optional[float] = None, block_q: int = 128,
                    block_k: int = 128, interpret: bool = False):
    """q [b, sq, h, hd]; k, v [b, sk, kvh, hd] -> [b, sq, h, hd]."""
    b, sq, h, hd = q.shape
    _, sk, kvh, _ = k.shape
    group = h // kvh
    scale = hd ** -0.5 if scale is None else scale

    bq = min(block_q, sq)
    bk = min(block_k, sk)
    pad_q = (-sq) % bq
    pad_k = (-sk) % bk
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = sq + pad_q, sk + pad_k

    qt = q.transpose(0, 2, 1, 3)       # [b, h, sq, hd]
    kt = k.transpose(0, 2, 1, 3)       # [b, kvh, sk, hd]
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, h, sq_p // bq, sk_p // bk)
    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window, softcap=softcap,
        bq=bq, bk=bk, seq_k=sk)
    out = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
            pl.BlockSpec((1, 1, bk, hd),
                         lambda bi, hi, qi, ki, g=group: (bi, hi // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda bi, hi, qi, ki: (bi, hi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq,), jnp.float32),      # m (running max)
            pltpu.VMEM((bq,), jnp.float32),      # l (running denom)
            pltpu.VMEM((bq, hd), jnp.float32),   # acc
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(qt, kt, vt)
    out = out.transpose(0, 2, 1, 3)
    if pad_q:
        out = out[:, :sq]
    return out


# ---------------------------------------------------------------------------
# paged decode attention (serving path)
# ---------------------------------------------------------------------------
def _paged_kernel(tables_ref, pos_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, softcap: float,
                  page: int, nb: int):
    bi = pl.program_id(0)
    pi = pl.program_id(2)

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(pi * page <= pos_ref[bi])
    def _block():
        q = q_ref[0, 0].astype(jnp.float32) * scale          # [g, hd]
        k = k_ref[0, :, 0].astype(jnp.float32)               # [page, hd]
        v = v_ref[0, :, 0].astype(jnp.float32)               # [page, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # [g, page]
        if softcap:
            s = softcap * jnp.tanh(s / softcap)
        k_pos = pi * page + jax.lax.broadcasted_iota(
            jnp.int32, (q.shape[0], page), 1)
        s = jnp.where(k_pos <= pos_ref[bi], s, NEG_INF)

        m_prev = m_scr[...]
        l_prev = l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
        p = jnp.exp(s - m_new[:, None])
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_prev * corr + jnp.sum(p, axis=1)
        m_scr[...] = m_new
        acc_scr[...] = (acc_scr[...] * corr[:, None]
                        + jax.lax.dot_general(
                            p, v, (((1,), (0,)), ((), ()))))

    @pl.when(pi == nb - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l[:, None]).astype(o_ref.dtype)


def paged_flash_decode(q, k_pages, v_pages, tables, pos, *,
                       softcap: float = 0.0, scale: Optional[float] = None,
                       interpret: bool = False):
    """Single-token decode attention reading the KV cache through a block
    table — the Pallas counterpart of
    :func:`repro.models.attention.paged_decode_attention`.

    q [b, 1, h, hd]; k_pages/v_pages [P, page, kvh, hd];
    tables [b, nb] int32 (physical page of logical block i); pos [b].

    The block table rides in as a *scalar-prefetch* operand
    (``PrefetchScalarGridSpec``): the kv ``index_map`` dereferences
    ``tables[bi, pi]`` so the pipeline DMAs exactly the pages each slot
    maps — the gather never materializes a dense [b, S] cache view in HBM.
    The page axis is sequential ('arbitrary') and carries online-softmax
    state in VMEM scratch; pages wholly beyond ``pos`` are skipped.
    Unmapped table entries point at the reserved null page 0 and are
    position-masked.  Global attention only (ring buffers stay dense);
    small head dims are interpret-mode exact but would want lane padding
    on real hardware."""
    b, _, h, hd = q.shape
    npages, page, kvh, _ = k_pages.shape
    nb = tables.shape[1]
    g = h // kvh
    scale = hd ** -0.5 if scale is None else scale

    qt = q.reshape(b, kvh, g, hd)
    flat_tables = tables.reshape(-1).astype(jnp.int32)
    kern = functools.partial(_paged_kernel, scale=scale, softcap=softcap,
                             page=page, nb=nb)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, kvh, nb),
        in_specs=[
            pl.BlockSpec((1, 1, g, hd),
                         lambda bi, ki, pi, tbl, p_: (bi, ki, 0, 0)),
            pl.BlockSpec((1, page, 1, hd),
                         lambda bi, ki, pi, tbl, p_, n=nb:
                         (tbl[bi * n + pi], 0, ki, 0)),
            pl.BlockSpec((1, page, 1, hd),
                         lambda bi, ki, pi, tbl, p_, n=nb:
                         (tbl[bi * n + pi], 0, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, g, hd),
                               lambda bi, ki, pi, tbl, p_: (bi, ki, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((g,), jnp.float32),       # m (running max)
            pltpu.VMEM((g,), jnp.float32),       # l (running denom)
            pltpu.VMEM((g, hd), jnp.float32),    # acc
        ],
    )
    out = pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(flat_tables, pos.astype(jnp.int32), qt, k_pages, v_pages)
    return out.reshape(b, 1, h, hd)
