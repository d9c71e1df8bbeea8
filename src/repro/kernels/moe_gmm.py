"""Grouped (per-expert) matmul Pallas kernel for the MoE dispatch path.

Grid (expert, row_block, col_block, k_block); k sequential with a VMEM
accumulator, so each [C, D] x [D, F] expert product streams K in
MXU-aligned tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(x_ref, w_ref, o_ref, acc_scr, *, nk: int):
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    x = x_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    acc_scr[...] += jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())))

    @pl.when(ki == nk - 1)
    def _fin():
        o_ref[0] = acc_scr[...].astype(o_ref.dtype)


def moe_gmm(x, w, *, block_c: int = 128, block_f: int = 128,
            block_k: int = 512, interpret: bool = False):
    """x [E, C, D] @ w [E, D, F] -> [E, C, F] per expert."""
    e, c, d = x.shape
    _, _, f = w.shape
    bc = min(block_c, c)
    bf = min(block_f, f)
    bk = min(block_k, d)
    assert c % bc == 0 and f % bf == 0 and d % bk == 0, (c, f, d)
    nk = d // bk
    return pl.pallas_call(
        functools.partial(_kernel, nk=nk),
        grid=(e, c // bc, f // bf, nk),
        in_specs=[
            pl.BlockSpec((1, bc, bk), lambda ei, ci, fi, ki: (ei, ci, ki)),
            pl.BlockSpec((1, bk, bf), lambda ei, ci, fi, ki: (ei, ki, fi)),
        ],
        out_specs=pl.BlockSpec((1, bc, bf),
                               lambda ei, ci, fi, ki: (ei, ci, fi)),
        out_shape=jax.ShapeDtypeStruct((e, c, f), x.dtype),
        scratch_shapes=[pltpu.VMEM((bc, bf), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(x, w)
