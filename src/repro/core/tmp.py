"""Megatron-style TMP primitives with Oases semantics, for use inside
``shard_map`` bodies.

``tmp_reduce`` is the TMP AllReduce (Megatron g): a *raw* ``lax.psum`` whose
output is tagged ``checkpoint_name(.., COLLECTIVE_NAME)``.  Combined with
the ``save_only_these_names`` remat policy in :mod:`repro.core.remat`, the
saved residual set is exactly the collective outputs, so rematerialization
never re-executes a TMP collective — the paper's fine-grained recomputation
(§3.2, justified by Eq. 1: ∂y/∂x_i = 1 makes the forward AllReduce output a
sufficient residual) realized as a JAX remat policy.

Gradient convention: ``shard_map``'s transpose uses partial cotangents
(see ``reduce_from_tmp``), under which no Megatron-f operator is needed and
``psum`` transposes to ``psum``.  The sequence-parallel (SP) pair
``sp_all_gather``/``sp_reduce_scatter`` and the slice ``batch_split`` are
custom-VJPs consistent with that convention.
"""
from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.ad_checkpoint import checkpoint_name


COLLECTIVE_NAME = "oases_collective"
Axes = Tuple[str, ...]


# --------------------------------------------------------------------------
# core collectives
# --------------------------------------------------------------------------
# NOTE: there is intentionally no ``copy_to_tmp`` (Megatron f).  Under
# shard_map's partial-cotangent convention an identity-fwd/psum-bwd operator
# at column-parallel inputs would double-count: the boundary transpose
# already psums parameter gradients over their replicated axes, and
# activation cotangents are *supposed* to stay partial inside the region.


def reduce_from_tmp(x, axes: Axes):
    """AllReduce forward (Megatron g) — deliberately a *raw* ``lax.psum``.

    Backward: ``shard_map``'s transpose uses the partial-cotangent convention
    (cotangents of replicated tensors are per-shard partial sums; the
    shard_map boundary inserts the final psum for parameters), under which
    ``psum`` transposes to ``psum``.  The per-layer collective count is
    identical to Megatron's f/g pair — 2 AllReduces forward, 2 backward —
    attached to g instead of f.  Eq. (1) (∂y/∂x_i = 1) is what makes the
    *forward* AllReduce's output a sufficient residual: with the fine-grained
    remat policy saving it (see tmp_reduce), the rematerialized subgraph
    contains no collective at all.

    Kept as a plain primitive (NOT custom_vjp) so the remat policy can see
    through it — a custom_vjp call is opaque to ``save_only_these_names`` and
    would be replayed during recomputation, defeating §3.2.
    """
    return lax.psum(x, axes) if axes else x


def tmp_reduce(x, axes: Axes, name: str = COLLECTIVE_NAME):
    """AllReduce + name the output for the fine-grained remat policy."""
    # named_scope: trace-time only — tags the psum in HLO metadata so the
    # reduce phase is attributable in XLA profiles (repro.obs.tracing)
    with jax.named_scope("tmp.reduce"):
        return checkpoint_name(reduce_from_tmp(x, axes), name)


# --------------------------------------------------------------------------
# sequence-parallel variants (beyond-paper: Megatron-SP AG/RS comm scheme)
# --------------------------------------------------------------------------
@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sp_all_gather(x, axes: Axes, dim: int):
    with jax.named_scope("tmp.sp_all_gather"):
        return lax.all_gather(x, axes, axis=dim, tiled=True) if axes else x


def _spag_fwd(x, axes, dim):
    return sp_all_gather(x, axes, dim), None


def _spag_bwd(axes, dim, _, g):
    return (lax.psum_scatter(g, axes, scatter_dimension=dim, tiled=True)
            if axes else g,)


sp_all_gather.defvjp(_spag_fwd, _spag_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def sp_reduce_scatter(x, axes: Axes, dim: int):
    with jax.named_scope("tmp.sp_reduce_scatter"):
        return (lax.psum_scatter(x, axes, scatter_dimension=dim, tiled=True)
                if axes else x)


def _sprs_fwd(x, axes, dim):
    return sp_reduce_scatter(x, axes, dim), None


def _sprs_bwd(axes, dim, _, g):
    return (lax.all_gather(g, axes, axis=dim, tiled=True) if axes else g,)


sp_reduce_scatter.defvjp(_sprs_fwd, _sprs_bwd)


@partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def batch_split(x, axes: Axes, dim: int):
    """Keep this shard's chunk of dim (planner-mode degree-down reshard).

    Forward is a free local slice; backward is the AllGather that reassembles
    the full-batch gradient (each shard holds a disjoint chunk, and the
    pre-split tensor was replicated over ``axes``)."""
    if not axes:
        return x
    import math
    sz = math.prod(lax.axis_size(a) for a in axes)
    chunk = x.shape[dim] // sz
    return lax.dynamic_slice_in_dim(x, axes_index(axes) * chunk, chunk,
                                    axis=dim)


def _bs_fwd(x, axes, dim):
    return batch_split(x, axes, dim), None


def _bs_bwd(axes, dim, _, g):
    # Partial-cotangent convention: the pre-split tensor was REPLICATED over
    # ``axes``, so each shard returns only its own chunk's cotangent placed
    # at its offset (zeros elsewhere); the shard-sum reassembles the full
    # gradient.  (An all_gather here would overcount by |axes| once the
    # shard_map boundary psums replicated-parameter grads.)
    if not axes:
        return (g,)
    import math
    sz = math.prod(lax.axis_size(a) for a in axes)
    chunk = g.shape[dim]
    full_shape = g.shape[:dim] + (chunk * sz,) + g.shape[dim + 1:]
    zeros = jnp.zeros(full_shape, g.dtype)
    return (lax.dynamic_update_slice_in_dim(
        zeros, g, axes_index(axes) * chunk, axis=dim),)


batch_split.defvjp(_bs_fwd, _bs_bwd)


# --------------------------------------------------------------------------
# axis index helpers (SPMD-traced)
# --------------------------------------------------------------------------
def axes_index(axes: Axes):
    """Linearized index of this shard within the given (ordered) axes."""
    if not axes:
        return jnp.int32(0)
    idx = jnp.int32(0)
    for a in axes:
        idx = idx * lax.axis_size(a) + lax.axis_index(a)
    return idx


def axes_size(axes: Axes) -> int:
    import math
    return math.prod(lax.axis_size(a) for a in axes) if axes else 1


# --------------------------------------------------------------------------
# the "pass barrier" used to emulate Merak's recompute/backward barriers
# --------------------------------------------------------------------------
@jax.custom_vjp
def pass_barrier(x):
    """Identity forward; optimization_barrier on the gradient.  Emulates the
    inter-pass barriers of layer-granularity recomputation schedules (Merak)
    so the A/B vs the barrier-free Oases cross-pass schedule is visible in
    the emitted HLO."""
    return x


def _pb_fwd(x):
    return x, None


def _pb_bwd(_, g):
    return (lax.optimization_barrier(g),)


pass_barrier.defvjp(_pb_fwd, _pb_bwd)


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------
def rms_norm(x, scale, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * lax.rsqrt(var + eps)
    return (y * (1.0 + scale.astype(jnp.float32))).astype(dt)


# --------------------------------------------------------------------------
# vocab-parallel embedding + cross-entropy (Megatron-style, chunked)
# --------------------------------------------------------------------------
def vocab_parallel_embed(tokens, embed_local, axes: Axes, *,
                         sp_seq_dim=None):
    """tokens [..] int32 (replicated over tp); embed_local [V/tp, D].
    ``sp_seq_dim``: sequence-parallel mode — the completing collective is a
    reduce-scatter along that dim instead of an AllReduce."""
    v_local = embed_local.shape[0]
    offset = axes_index(axes) * v_local
    local_tok = tokens - offset
    in_shard = (local_tok >= 0) & (local_tok < v_local)
    local_tok = jnp.clip(local_tok, 0, v_local - 1)
    out = jnp.take(embed_local, local_tok, axis=0)
    out = jnp.where(in_shard[..., None], out, jnp.zeros_like(out))
    if sp_seq_dim is not None and axes:
        return checkpoint_name(sp_reduce_scatter(out, axes, sp_seq_dim),
                               COLLECTIVE_NAME)
    return tmp_reduce(out, axes)


def _xent_chunk(x, head_local, labels, axes: Axes, softcap: float):
    """x [t, D]; head_local [D, V/tp]; labels [t] -> (sum_nll[t])."""
    logits = jnp.dot(x.astype(jnp.float32), head_local.astype(jnp.float32))
    if softcap:
        logits = softcap * jnp.tanh(logits / softcap)
    v_local = logits.shape[-1]
    offset = axes_index(axes) * v_local
    # stable log-sum-exp across vocab shards (max is stability-only, so the
    # pmax sees only a stopped-gradient constant — pmax has no JVP rule)
    m_local = lax.stop_gradient(jnp.max(logits, axis=-1))
    m = lax.pmax(m_local, axes) if axes else m_local
    z_local = jnp.sum(jnp.exp(logits - m[..., None]), axis=-1)
    z = reduce_from_tmp(z_local, axes)
    local_lab = labels - offset
    in_shard = (local_lab >= 0) & (local_lab < v_local)
    local_lab = jnp.clip(local_lab, 0, v_local - 1)
    lab_logit_local = jnp.take_along_axis(
        logits, local_lab[..., None], axis=-1)[..., 0]
    lab_logit_local = jnp.where(in_shard, lab_logit_local, 0.0)
    lab_logit = reduce_from_tmp(lab_logit_local, axes)
    return jnp.log(z) + m - lab_logit


def vocab_parallel_xent(x, head_local, labels, axes: Axes, *,
                        chunk: int = 512, softcap: float = 0.0,
                        mask=None):
    """Chunked vocab-parallel cross entropy.

    Never materializes [tokens, V]; each seq chunk's logits live only inside a
    rematerialized scan step (beyond-paper memory optimization — the paper's
    models cap at V=50k where the full logit tensor still fits).

    x [b, s, D]; head_local [D, V/tp]; labels [b, s] -> (loss_sum, count).
    """
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)
    lf = labels.reshape(t)
    mf = mask.reshape(t) if mask is not None else jnp.ones((t,), jnp.float32)
    chunk = min(chunk, t)
    n = t // chunk
    rem = t - n * chunk

    @jax.checkpoint
    def step(carry, inp):
        xc, lc, mc = inp
        nll = _xent_chunk(xc, head_local, lc, axes, softcap)
        return carry + jnp.sum(nll * mc), None

    init = jnp.zeros((), jnp.float32)
    if n:
        xs = (xf[:n * chunk].reshape(n, chunk, d),
              lf[:n * chunk].reshape(n, chunk),
              mf[:n * chunk].reshape(n, chunk))
        init, _ = lax.scan(step, init, xs)
    if rem:
        nll = _xent_chunk(xf[n * chunk:], head_local, lf[n * chunk:], axes,
                          softcap)
        init = init + jnp.sum(nll * mf[n * chunk:])
    return init, jnp.sum(mf)
