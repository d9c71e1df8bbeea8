"""Unit + property tests for the TMP primitives (single-device: the
collective axes are empty tuples, which must degrade to identity)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.core import tmp as tmpc


def test_reduce_from_tmp_no_axes_identity():
    x = jnp.arange(6.0)
    np.testing.assert_array_equal(tmpc.reduce_from_tmp(x, ()), x)


def test_vocab_parallel_xent_matches_dense():
    k = jax.random.PRNGKey(0)
    t, d, v = 64, 32, 97
    x = jax.random.normal(k, (2, t // 2, d))
    head = jax.random.normal(jax.random.PRNGKey(1), (d, v))
    labels = jax.random.randint(jax.random.PRNGKey(2), (2, t // 2), 0, v)
    loss_sum, count = tmpc.vocab_parallel_xent(x, head, labels, (), chunk=16)
    logits = (x.reshape(-1, d) @ head).astype(jnp.float32)
    dense = -jax.nn.log_softmax(logits)[jnp.arange(t), labels.reshape(-1)]
    np.testing.assert_allclose(float(loss_sum), float(jnp.sum(dense)),
                               rtol=1e-5)
    assert int(count) == t


def test_xent_gradient_matches_dense():
    k = jax.random.PRNGKey(3)
    t, d, v = 16, 8, 23
    x = jax.random.normal(k, (1, t, d))
    head = jax.random.normal(jax.random.PRNGKey(4), (d, v))
    labels = jax.random.randint(jax.random.PRNGKey(5), (1, t), 0, v)

    def ours(h):
        s, c = tmpc.vocab_parallel_xent(x, h, labels, (), chunk=5)
        return s / c

    def dense(h):
        logits = (x.reshape(-1, d) @ h).astype(jnp.float32)
        return jnp.mean(-jax.nn.log_softmax(logits)[jnp.arange(t),
                                                    labels.reshape(-1)])

    g1 = jax.grad(ours)(head)
    g2 = jax.grad(dense)(head)
    np.testing.assert_allclose(g1, g2, atol=1e-5, rtol=1e-4)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 64), st.integers(2, 40))
def test_xent_positive_and_bounded(t, v):
    x = jax.random.normal(jax.random.PRNGKey(t), (1, t, 8))
    head = jax.random.normal(jax.random.PRNGKey(v), (8, v))
    labels = jax.random.randint(jax.random.PRNGKey(7), (1, t), 0, v)
    s, c = tmpc.vocab_parallel_xent(x, head, labels, (), chunk=7)
    nll = float(s / c)
    assert 0.0 <= nll < 50.0


def test_softcap_bounds_logits_effect():
    x = jnp.ones((1, 4, 8)) * 100.0
    head = jnp.ones((8, 16))
    labels = jnp.zeros((1, 4), jnp.int32)
    s_cap, _ = tmpc.vocab_parallel_xent(x, head, labels, (), softcap=30.0)
    assert np.isfinite(float(s_cap))


def test_rms_norm_unit_output():
    x = jax.random.normal(jax.random.PRNGKey(0), (4, 64)) * 13.0
    y = tmpc.rms_norm(x, jnp.zeros((64,)))
    ms = jnp.mean(jnp.square(y.astype(jnp.float32)), axis=-1)
    np.testing.assert_allclose(ms, jnp.ones_like(ms), rtol=1e-3)


# --------------------------------------------------------------------------
# Alg. 1's reduction order (core/schedule.py): traced over an abstract mesh,
# so the 2-way model group needs no second device
# --------------------------------------------------------------------------
def _forward_barriers(model: int, schedule: str) -> int:
    from jax.sharding import AbstractMesh

    from repro.configs.base import TrainHParams
    from repro.configs.registry import get_config
    from repro.models import lm
    from repro.models import params as prm

    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    mesh = AbstractMesh((1, model), ("data", "model"))
    fn, specs, _ = lm.build_train_loss(cfg, mesh,
                                       TrainHParams(schedule=schedule),
                                       global_batch=4, seq_len=64)
    params = jax.eval_shape(lambda: prm.init_params(specs,
                                                    jax.random.PRNGKey(0)))
    batch = {k: jax.ShapeDtypeStruct((4, 64), jnp.int32)
             for k in ("tokens", "labels")}
    with jax.sharding.use_abstract_mesh(mesh):
        jx = jax.make_jaxpr(lambda p, b: fn(p, b)[0])(params, batch)
    return str(jx).count("optimization_barrier")


@pytest.mark.parametrize("model,schedule,barriers", [
    # a layer body of attention + MLP over two sub-batches issues four
    # reductions; each after the first waits for the one before it
    (2, "oases", 3),
    (2, "merak", 3),
    # no TMP axes: no reduction to order
    (1, "oases", 0),
    # one batch: nothing to keep apart
    (2, "megatron", 0),
], ids=["oases-tp2", "merak-tp2", "oases-tp1", "megatron-tp2"])
def test_sub_batch_reductions_are_ordered(model, schedule, barriers):
    assert _forward_barriers(model, schedule) == barriers


def test_reduction_order_mirrors_backward():
    """The barrier transposes to a barrier on the cotangents, so the
    backward reductions are ordered too, with no custom VJP."""
    from jax.sharding import AbstractMesh, PartitionSpec as P

    from repro.core.axes import MeshInfo
    from repro.core.schedule import TmpCtx, apply_layer, split_tree

    mesh = AbstractMesh((1, 2), ("data", "model"))
    ctx = TmpCtx(MeshInfo(mesh, ("data",), ("model",)), schedule="oases")

    def part(p, x, a):
        return ctx.row_matmul(jnp.tanh(x @ p["w1"]), p["w2"]), 0.0

    def layer(p, x):
        xs, _ = apply_layer([part, part], p, split_tree(x, 2), [{}, {}],
                            "oases")
        return jnp.concatenate(xs)

    sharded = jax.shard_map(
        layer, mesh=mesh,
        in_specs=({"w1": P(None, "model"), "w2": P("model", None)}, P()),
        out_specs=P(), check_vma=False)
    p = {"w1": jax.ShapeDtypeStruct((8, 16), jnp.float32),
         "w2": jax.ShapeDtypeStruct((16, 8), jnp.float32)}
    x = jax.ShapeDtypeStruct((4, 8), jnp.float32)
    fwd = str(jax.make_jaxpr(sharded)(p, x))
    grad = str(jax.make_jaxpr(
        jax.grad(lambda p, x: sharded(p, x).sum()))(p, x))
    assert fwd.count("optimization_barrier") == 3
    assert grad.count("optimization_barrier") == 2 * 3


def test_reduction_order_changes_no_number():
    from repro.core.schedule import _ReduceOrder

    def f(xs, ordered):
        order = _ReduceOrder()
        outs = [order.reduce(lambda t: 2.0 * t, x) if ordered else 2.0 * x
                for x in xs]
        return sum(jnp.sum(jnp.sin(o) * (i + 1)) for i, o in enumerate(outs))

    xs = [jax.random.normal(jax.random.PRNGKey(i), (4, 8)) for i in range(3)]
    for fn in (f, jax.grad(f)):
        ours = jax.jit(fn, static_argnums=1)(xs, True)
        ref = jax.jit(fn, static_argnums=1)(xs, False)
        for a, b in zip(jax.tree_util.tree_leaves(ours),
                        jax.tree_util.tree_leaves(ref)):
            np.testing.assert_array_equal(a, b)
