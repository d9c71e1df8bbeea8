"""The four TMP training schedules (paper §3, Fig. 3, Alg. 1–2).

All schedules are expressed as *program structure*.  Independence alone
does not make the TPU compiler overlap anything: its all-reduce combiner
merges independent sub-batch reductions into one, and by default an
all-reduce runs synchronously, holding the TensorCore.  So the split
schedules also fix Alg. 1's order: ``apply_layer`` ties each reduction to
the output of the one emitted before it with an ``optimization_barrier``
on the partial product, which keeps the reductions apart and leaves the
matmul that makes it free to run under the previous reduction.  On TPU the
train step compiles its TMP reductions asynchronously
(:func:`repro.launch.steps.jit_train_step`), and the
latency-hiding scheduler places each under the next sub-batch's compute
(DESIGN.md §2):

* ``megatron`` — Fig. 3a: one batch, strictly sequential blocked AllReduce.
* ``wang``     — Wang et al. [53]: decompose each row-parallel matmul into
  chunks so chunk i's AllReduce overlaps chunk i+1's matmul (intra-op).
* ``merak``    — Fig. 3b: two sub-batches pipelined, but pass barriers remain
  (emulated with an optimization_barrier on layer gradients) and
  recomputation re-executes collectives (coarse remat).
* ``oases``    — Fig. 3c/d: two sub-batches, cross-pass (barrier-free; the
  transposed backward interleaves recompute and backward the same way), and
  with ``fine_remat`` the recompute contains no collectives at all.
* ``fused``    — beyond-paper: kernel-level collective matmul
  (:mod:`repro.kernels.collective_matmul`).  Each TMP collective is a ring
  streamed through its producing/consuming matmul, so every ring step's
  transfer overlaps the next tile's compute by construction — no scheduler
  heuristics involved.  ``use_pallas=True`` swaps the ``lax.ppermute`` ring
  for the in-kernel RDMA Pallas version on TPU.
"""
from __future__ import annotations

import contextlib
import contextvars
from dataclasses import dataclass
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import tmp as tmpc
from repro.core.axes import MeshInfo
from repro.obs.tracing import phase_scope

SCHEDULES = ("megatron", "wang", "merak", "oases", "fused")


class _ReduceOrder:
    """Alg. 1's order of one layer's TMP reductions, kept at trace time:
    each reduction waits for the one issued before it."""

    def __init__(self):
        self.last = None

    def reduce(self, fn: Callable, x):
        if self.last is not None:
            # only the partial product waits, not the matmul that made it:
            # that matmul is the window the previous reduction hides under,
            # and the combiner can no longer merge the two reductions.  The
            # barrier transposes to a barrier on the cotangents, so the
            # backward reductions get the mirrored order.
            _, x = lax.optimization_barrier((self.last, x))
        self.last = fn(x)
        return self.last


_REDUCE_ORDER: contextvars.ContextVar = contextvars.ContextVar(
    "tmp_reduce_order", default=None)


@contextlib.contextmanager
def _reduce_order(split: int):
    """Order the TMP reductions of the layer traced inside the block when
    its batch is split into ``split`` > 1 sub-batches."""
    token = _REDUCE_ORDER.set(_ReduceOrder() if split > 1 else None)
    try:
        yield
    finally:
        _REDUCE_ORDER.reset(token)


def _in_order(fn: Callable, x, axes):
    """``fn(x)`` — a TMP reduction over ``axes`` — in the order of the
    layer being traced, if any and if ``axes`` reduce anything."""
    order = _REDUCE_ORDER.get()
    return fn(x) if order is None or not axes else order.reduce(fn, x)


@dataclass(frozen=True)
class TmpCtx:
    """Per-layer TMP context: axes + communication scheme.

    ``seq_parallel`` (beyond-paper, Megatron-SP): activations between TMP
    blocks are sharded along the sequence dim; the block entry all-gathers
    and the block exit reduce-scatters (same link bytes as the AllReduce,
    but rematerialization residuals shrink by tp — see EXPERIMENTS §Perf).

    ``seq_shard`` > 1 (beyond-paper, ring attention — DESIGN.md §12): the
    attention parts keep activations sequence-sharded *through* the mixer
    instead of gathering at the block entry.  Attention weights are
    replicated over the model group (full heads per device) and the KV
    shards circulate around the TMP ring
    (:mod:`repro.kernels.ring_attention`); the MLP/recurrent parts still
    run Megatron-SP.  Requires ``seq_parallel=True`` and
    ``seq_shard == tp_total``.

    ``layout`` selects the partition dimensionality.  ``"auto"`` follows the
    mesh/degree (a ``model_y`` axis or tuple degree activates the 2D hybrid
    layout); ``"1d"`` forces the classic layout, treating a multi-axis model
    group as one flattened ring group.  In 2D, weight *width* shards over
    the x-axes and the *contraction* dim (d_model) over the y-axes; the
    row/gather matmuls decompose into per-axis collectives (fused: per-axis
    rings) — see DESIGN.md §2D hybrid partition.
    """
    info: MeshInfo
    degree: Optional[object] = None   # None | int | (dx, dy)
    schedule: str = "oases"
    wang_chunks: int = 4
    use_pallas: bool = False
    seq_parallel: bool = False
    seq_shard: int = 1                # ring-attention seq shards (1 = off)
    layout: str = "auto"              # auto | 1d | 2d

    def _axes_xy(self) -> Tuple[Tuple[str, ...], Tuple[str, ...]]:
        if self.layout == "1d":
            from repro.core.axes import deg_total
            return self.info.tp_axes(deg_total(self.degree)), ()
        return self.info.xy_axes(self.degree)

    @property
    def x_axes(self) -> Tuple[str, ...]:
        return self._axes_xy()[0]

    @property
    def y_axes(self) -> Tuple[str, ...]:
        return self._axes_xy()[1]

    @property
    def is_2d(self) -> bool:
        return bool(self.y_axes)

    @property
    def tp_axes(self) -> Tuple[str, ...]:
        ax, ay = self._axes_xy()
        return ax + ay

    def _size(self, axes: Tuple[str, ...]) -> int:
        import math
        s = dict(self.info.mesh.shape)
        return math.prod(s[a] for a in axes) if axes else 1

    @property
    def tp(self) -> int:
        """The *width*-sharding degree (heads / d_ff divide by this) — dx in
        2D, the whole group in 1D."""
        return self._size(self.x_axes)

    @property
    def tp_y(self) -> int:
        return self._size(self.y_axes)

    @property
    def tp_total(self) -> int:
        return self._size(self.tp_axes)

    def reduce(self, x, seq_dim: int = 1):
        if self.seq_parallel and self.tp_axes:
            from jax.ad_checkpoint import checkpoint_name
            return _in_order(lambda t: checkpoint_name(
                tmpc.sp_reduce_scatter(t, self.tp_axes, seq_dim),
                tmpc.COLLECTIVE_NAME), x, self.tp_axes)
        return _in_order(lambda t: tmpc.tmp_reduce(t, self.tp_axes), x,
                         self.tp_axes)

    def gather_seq(self, x, seq_dim: int = 1):
        """Block entry in SP mode: reassemble the full sequence."""
        if self.seq_parallel and self.tp_axes:
            return tmpc.sp_all_gather(x, self.tp_axes, seq_dim)
        return x

    def shard_seq(self, x, seq_dim: int = 1):
        """Slice a replicated tensor down to this shard's sequence chunk
        (used where the block had no trailing collective)."""
        if self.seq_parallel and self.tp_axes:
            return tmpc.batch_split(x, self.tp_axes, seq_dim)
        return x

    def proj(self, x, w):
        """Column-parallel entry projection ``x @ w``.

        1D: a plain local dot (w's contraction dim is replicated).  2D: w's
        contraction dim is y-sharded — slice x's matching chunk locally
        (free: x is replicated over y) and AllReduce the partial products
        over the y-axes.  In fused mode the psum becomes a collective-matmul
        ring so the y-transfers hide under the tile matmuls.  Detection is
        shape-driven so per-weight divisibility fallbacks (replicated specs)
        compose: a full-row weight always takes the plain-dot path.
        """
        with phase_scope(f"tmp.{self.schedule}.proj"):
            if self.y_axes and w.shape[0] != x.shape[-1]:
                from jax.ad_checkpoint import checkpoint_name
                xy = tmpc.batch_split(x, self.y_axes, x.ndim - 1)
                if self.schedule == "fused" and xy.ndim >= 2:
                    from repro.kernels import collective_matmul as cm
                    y = cm.fused_matmul_allreduce(
                        xy, w, self.y_axes,
                        scatter_dim=self._ring_dim(xy, min(1, xy.ndim - 2),
                                                   self.y_axes),
                        use_pallas=self.use_pallas)
                    return checkpoint_name(y, tmpc.COLLECTIVE_NAME)
                return tmpc.tmp_reduce(jnp.dot(xy, w), self.y_axes)
            return jnp.dot(x, w)

    def contract_reduce(self, t, partial: bool = True):
        """Finish a y-contracted product computed outside :meth:`proj`
        (e.g. the sliced-kv einsum in blocks._qkv): AllReduce over y."""
        if partial and self.y_axes:
            return tmpc.tmp_reduce(t, self.y_axes)
        return t

    def contract_slice(self, x, w_rows: int):
        """x's local chunk of a y-sharded contraction dim (``w_rows`` = the
        weight's leading dim); identity when the weight has full rows."""
        if self.y_axes and w_rows != x.shape[-1]:
            return tmpc.batch_split(x, self.y_axes, x.ndim - 1), True
        return x, False

    def _ring_dim(self, x, preferred: int, axes: Tuple[str, ...]) -> int:
        """Chunking dim for the fused all-reduce rings.

        Training activations ring over the sequence dim; at decode shapes
        the sequence dim is 1 (a single token), so the ring would silently
        fall back to the blocking reference.  The all-reduce flavour is free
        to chunk along ANY non-contraction dim (the output is replicated
        either way), so when the preferred dim has collapsed to 1 we stream
        the ring over the slot-batch dim instead — this is what keeps
        ``schedule="fused"`` overlapping at batch-1 decode shapes.  Dims
        that the group size does not divide are left to the kernel's own
        reference fallback.
        """
        if x.shape[preferred] != 1:
            return preferred
        n = self._size(axes)
        for dim in range(x.ndim - 1):
            if dim != preferred and n > 1 and x.shape[dim] % n == 0:
                return dim
        return preferred

    def row_matmul(self, x, w, seq_dim: int = 1, full_out: Optional[int] = None):
        """x [..., K_local] @ w [K_local, D] followed by AllReduce (or
        reduce-scatter in SP mode).

        'wang' decomposes along the second-to-last dim so the chunked
        AllReduces pipeline against the remaining chunk matmuls; 'fused'
        goes one level further and streams the matmul tiles through a ring
        collective kernel (guaranteed overlap).  The AllReduce flavour
        falls back to the blocking reference for indivisible shapes /
        multi-axis groups; the SP reduce-scatter flavour requires the seq
        dim divisible by the group (guaranteed by the SP gate in
        models/lm.py, which only enables SP when seq % tp == 0).

        2D layout: the collective decomposes per axis — AllReduce the
        partial sums over the x-axes (K is x-sharded), then all-gather the
        y-sharded output columns back to ``full_out`` when the exit weight
        shards them.  Both collective outputs are checkpoint-named so the
        fine-remat recompute stays collective-free (§3.2).
        """
        with phase_scope(f"tmp.{self.schedule}.row_matmul"):
            if self.y_axes:
                from jax.ad_checkpoint import checkpoint_name
                if self.schedule == "fused" and self.x_axes and x.ndim >= 2:
                    from repro.kernels import collective_matmul as cm
                    y = cm.fused_matmul_allreduce(
                        x, w, self.x_axes,
                        scatter_dim=self._ring_dim(
                            x, min(seq_dim, x.ndim - 2), self.x_axes),
                        use_pallas=self.use_pallas)
                    y = checkpoint_name(y, tmpc.COLLECTIVE_NAME)
                else:
                    y = _in_order(
                        lambda t: tmpc.tmp_reduce(t, self.x_axes),
                        jnp.dot(x, w), self.x_axes)
                if full_out is not None and w.shape[-1] != full_out:
                    y = checkpoint_name(
                        tmpc.sp_all_gather(y, self.y_axes, y.ndim - 1),
                        tmpc.COLLECTIVE_NAME)
                return y
            if self.schedule == "fused" and self.tp_axes and x.ndim >= 2:
                from jax.ad_checkpoint import checkpoint_name
                from repro.kernels import collective_matmul as cm
                if self.seq_parallel:
                    y = cm.fused_matmul_reducescatter(
                        x, w, self.tp_axes, seq_dim, self.use_pallas)
                else:
                    y = cm.fused_matmul_allreduce(
                        x, w, self.tp_axes,
                        scatter_dim=self._ring_dim(
                            x, min(seq_dim, x.ndim - 2), self.tp_axes),
                        use_pallas=self.use_pallas)
                return checkpoint_name(y, tmpc.COLLECTIVE_NAME)
            if self.schedule == "wang" and not self.seq_parallel \
                    and x.ndim >= 2:
                n = self.wang_chunks
                dim = x.ndim - 2
                if x.shape[dim] % n == 0 and x.shape[dim] >= n:
                    chunks = jnp.split(x, n, axis=dim)
                    outs = [self.reduce(jnp.dot(c, w)) for c in chunks]
                    return jnp.concatenate(outs, axis=dim)
            return self.reduce(jnp.dot(x, w))

    def gather_matmul(self, x, ws, seq_dim: int = 1):
        """Column-parallel block entry: project ``x`` with every weight in
        ``ws`` (wq/wk/wv or wg/wu), gathering the sequence first in SP mode.

        In fused+SP mode one all-gather ring feeds all the matmuls,
        consuming shards as they arrive; otherwise gather once (SP) or
        not at all and apply plain dots.  2D: each weight's y-sharded
        contraction runs through :meth:`proj` (slice + per-axis ring).
        """
        ws = tuple(ws)
        with phase_scope(f"tmp.{self.schedule}.gather_matmul"):
            if self.y_axes:
                return tuple(self.proj(x, w) for w in ws)
            if self.schedule == "fused" and self.seq_parallel \
                    and self.tp_axes:
                from repro.kernels import collective_matmul as cm
                return cm.fused_allgather_matmul(x, ws, self.tp_axes,
                                                 seq_dim, self.use_pallas)
            h = self.gather_seq(x, seq_dim)
            return tuple(jnp.dot(h, w) for w in ws)


def split_tree(tree, split: int):
    """Split the leading (batch) dim of every leaf into `split` sub-batches."""
    def get(i):
        return jax.tree_util.tree_map(
            lambda t: t[i * (t.shape[0] // split):(i + 1) * (t.shape[0] // split)],
            tree)
    return [get(i) for i in range(split)]


def merge_tree(subs):
    return jax.tree_util.tree_map(
        lambda *ts: jnp.concatenate(ts, axis=0), *subs)


def effective_split(schedule: str, split: int, local_batch: int) -> int:
    """Sub-batch split factor: oases/merak split (paper: 2) when divisible.
    'fused' overlaps intra-op (inside the kernel), so like megatron/wang it
    runs the full batch in one pass."""
    if schedule not in SCHEDULES:
        # defense in depth: TrainHParams/ParallelPlan validate at
        # construction, but raw strings can still arrive here
        from repro.core.plan import validate_schedule
        validate_schedule(schedule)
    if schedule in ("megatron", "wang", "fused"):
        return 1
    s = min(split, local_batch)
    while s > 1 and local_batch % s:
        s -= 1
    return max(s, 1)


def apply_layer(parts: Sequence[Callable], p, xs: List, auxs: List,
                schedule: str):
    """Run one layer's residual parts over the sub-batches.

    Program order = Alg. 1: for each part, emit (compute_j, collective_j) for
    every sub-batch j before the residual adds, so collective_j is independent
    of compute_{j+1} — the overlap window.  With more than one sub-batch,
    each collective is also tied to the output of the one emitted before it
    (:class:`_ReduceOrder`), so all stay separate reductions in that order.
    Returns (xs, aux_scalar).
    """
    aux_total = jnp.float32(0.0)
    with _reduce_order(len(xs)):
        for part in parts:
            deltas = []
            for j, (x, a) in enumerate(zip(xs, auxs)):
                # sub-batch scope: Alg. 1's (compute_j, collective_j) chunks
                # are attributable per sub-batch in XLA profiles
                with phase_scope(f"tmp.{schedule}.sub{j}"):
                    d, aux = part(p, x, a)
                deltas.append(d)
                aux_total = aux_total + aux
            xs = [x + d for x, d in zip(xs, deltas)]
    if schedule == "merak":
        xs = [tmpc.pass_barrier(x) for x in xs]
    return xs, aux_total
