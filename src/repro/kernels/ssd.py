"""Mamba2 SSD (state-space duality) Pallas kernel.

Grid (batch, head, chunk); the chunk dimension is sequential and the
inter-chunk state S [hd, n] rides in VMEM scratch.  Per chunk:

  intra:  Y += tril(C B^T * seg_decay) @ (dt*X)     (quadratic inside chunk)
  inter:  Y += exp(cumlog_a) * (C @ S^T)
  state:  S  = chunk_decay * S + (decay_to_end * dt * X)^T @ B

This is the TPU-native chunking of the SSD recurrence: MXU-sized [Q, hd] x
[hd, n] tiles, no sequential elementwise scan in the hot path.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, dta_ref, dtx_ref, b_ref, c_ref, o_ref, s_scr, *, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    dta = dta_ref[0, 0].astype(jnp.float32)           # [q] log-decay
    xdt = dtx_ref[0, 0].astype(jnp.float32)           # [q, hd] dt*x
    B = b_ref[0].astype(jnp.float32)                  # [q, n]
    C = c_ref[0].astype(jnp.float32)                  # [q, n]

    la = jnp.cumsum(dta)                              # [q]
    la_last = la[-1]

    # intra-chunk quadratic
    seg = jnp.exp(la[:, None] - la[None, :])          # [q, q]
    iota_i = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    iota_j = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    seg = jnp.where(iota_j <= iota_i, seg, 0.0)
    cb = jax.lax.dot_general(C, B, (((1,), (1,)), ((), ())))     # [q, q]
    y = jax.lax.dot_general(cb * seg, xdt, (((1,), (0,)), ((), ())))

    # inter-chunk from carried state
    S = s_scr[...]                                    # [hd, n]
    y += jnp.exp(la)[:, None] * jax.lax.dot_general(
        C, S, (((1,), (1,)), ((), ())))               # [q, hd]

    # state update
    decay_to_end = jnp.exp(la_last - la)              # [q]
    s_scr[...] = (jnp.exp(la_last) * S
                  + jax.lax.dot_general(
                      xdt * decay_to_end[:, None], B,
                      (((0,), (0,)), ((), ()))))      # [hd, n]
    o_ref[0, 0] = y.astype(o_ref.dtype)


def ssd(x, dt, A_log, B, C, D, *, chunk: int = 128,
        interpret: bool = False):
    """x [b, s, h, p]; dt [b, s, h] (post-softplus); A_log [h]; B, C [b, s, n];
    D [h].  Returns y [b, s, h, p] (final state not returned — training path;
    decode uses models/ssd.ssd_step)."""
    b, s, h, p = x.shape
    n = B.shape[-1]
    q = min(chunk, s)
    assert s % q == 0

    a = -jnp.exp(A_log.astype(jnp.float32))
    dta = dt.astype(jnp.float32) * a[None, None, :]            # [b, s, h]
    dtx = x.astype(jnp.float32) * dt.astype(jnp.float32)[..., None]

    # layouts: per (batch, head): x [s, p]; B/C shared across heads
    dta_t = dta.transpose(0, 2, 1)                             # [b, h, s]
    dtx_t = dtx.transpose(0, 2, 1, 3)                          # [b, h, s, p]

    y = pl.pallas_call(
        functools.partial(_kernel, q=q),
        grid=(b, h, s // q),
        in_specs=[
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, 1, q), lambda bi, hi, ci: (bi, hi, ci)),
            pl.BlockSpec((1, 1, q, p), lambda bi, hi, ci: (bi, hi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
            pl.BlockSpec((1, q, n), lambda bi, hi, ci: (bi, ci, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, p),
                               lambda bi, hi, ci: (bi, hi, ci, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, s, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((p, n), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(dtx_t, dta_t, dtx_t, B, C)
    y = y.transpose(0, 2, 1, 3)
    y = y + D.astype(jnp.float32)[None, None, :, None] * x.astype(jnp.float32)
    return y.astype(x.dtype)
