"""RG-LRU Pallas kernel (RecurrentGemma's sequential hot loop).

Grid (width_blocks, time_blocks); the time dimension is sequential
('arbitrary') and the per-width-block recurrent state h lives in VMEM
scratch across time blocks — the HBM traffic is exactly x-in / y-out.
Within a block the recurrence runs as an unrolled elementwise chain over
bt steps (VPU work, no MXU).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


C_CONST = 8.0


def _kernel(x_ref, wa_ref, ba_ref, wx_ref, bx_ref, ap_ref, o_ref, h_scr, *,
            bt: int):
    ti = pl.program_id(1)

    @pl.when(ti == 0)
    def _init():
        h_scr[...] = jnp.zeros_like(h_scr)

    x = x_ref[0].astype(jnp.float32)           # [bt, bw]
    wa = wa_ref[...].astype(jnp.float32)
    ba = ba_ref[...].astype(jnp.float32)
    wx = wx_ref[...].astype(jnp.float32)
    bx = bx_ref[...].astype(jnp.float32)
    ap = ap_ref[...].astype(jnp.float32)

    r = jax.nn.sigmoid(x * wa[None] + ba[None])
    i = jax.nn.sigmoid(x * wx[None] + bx[None])
    log_a = -C_CONST * jax.nn.softplus(ap)[None] * r          # [bt, bw]
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-6)) * (i * x)

    h = h_scr[...]
    ys = []
    for t in range(bt):                       # unrolled within the block
        h = a[t] * h + gated[t]
        ys.append(h)
    h_scr[...] = h
    o_ref[0] = jnp.stack(ys).astype(o_ref.dtype)


def rglru(x, params, *, block_t: int = 64, block_w: int = 512,
          interpret: bool = False):
    """x [b, s, w] (conv'd input branch); params: w_a/b_a/w_x/b_x/a_param [w].
    Returns (y [b, s, w], h_last [b, w])."""
    b, s, w = x.shape
    bt = min(block_t, s)
    bw = min(block_w, w)
    assert s % bt == 0 and w % bw == 0, (s, bt, w, bw)

    def one_batch(xb):
        y = pl.pallas_call(
            functools.partial(_kernel, bt=bt),
            grid=(w // bw, s // bt),
            in_specs=[
                pl.BlockSpec((1, bt, bw), lambda wi, ti: (0, ti, wi)),
                pl.BlockSpec((bw,), lambda wi, ti: (wi,)),
                pl.BlockSpec((bw,), lambda wi, ti: (wi,)),
                pl.BlockSpec((bw,), lambda wi, ti: (wi,)),
                pl.BlockSpec((bw,), lambda wi, ti: (wi,)),
                pl.BlockSpec((bw,), lambda wi, ti: (wi,)),
            ],
            out_specs=pl.BlockSpec((1, bt, bw), lambda wi, ti: (0, ti, wi)),
            out_shape=jax.ShapeDtypeStruct((1, s, w), x.dtype),
            scratch_shapes=[pltpu.VMEM((bw,), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
        )(xb[None], params["w_a"], params["b_a"], params["w_x"],
          params["b_x"], params["a_param"])
        return y[0]

    y = jax.vmap(one_batch)(x)
    return y, y[:, -1].astype(jnp.float32)
