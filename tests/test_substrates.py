"""Data pipeline, checkpoint store, optimizer, straggler detector."""
import os
import tempfile

import jax
import jax.numpy as jnp
import numpy as np

from conftest import optional_hypothesis

given, settings, st = optional_hypothesis()

from repro.checkpoint import store
from repro.core.axes import mesh_info
from repro.data.pipeline import DataConfig, make_batch
from repro.models import params as prm
from repro.optim import adamw
from repro.runtime.trainer import StragglerDetector


# ---------------- data ----------------
def test_data_determinism():
    cfg = DataConfig(global_batch=4, seq_len=32, vocab_size=100)
    b1 = make_batch(cfg, 5)
    b2 = make_batch(cfg, 5)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    b3 = make_batch(cfg, 6)
    assert not np.array_equal(b1["tokens"], b3["tokens"])


def test_data_labels_shifted():
    cfg = DataConfig(global_batch=2, seq_len=16, vocab_size=50, pack=False)
    b = make_batch(cfg, 0)
    assert b["tokens"].shape == (2, 16) and b["labels"].shape == (2, 16)
    # labels are next-token: reconstruct the underlying stream
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_data_microbatch_reshape():
    cfg = DataConfig(global_batch=8, seq_len=16, vocab_size=50, microbatch=4)
    b = make_batch(cfg, 0)
    assert b["tokens"].shape == (4, 2, 16)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 1000), st.integers(1, 8))
def test_data_determinism_property(step, batch):
    cfg = DataConfig(global_batch=batch, seq_len=8, vocab_size=64)
    np.testing.assert_array_equal(make_batch(cfg, step)["tokens"],
                                  make_batch(cfg, step)["tokens"])
    assert make_batch(cfg, step)["tokens"].max() < 64


# ---------------- checkpoint ----------------
def test_checkpoint_roundtrip():
    tree = {"a": jnp.arange(6.0).reshape(2, 3),
            "b": {"c": jnp.ones((4,), jnp.int32), "d": None}}
    with tempfile.TemporaryDirectory() as d:
        store.save(d, 3, tree, metadata={"loss": 1.5})
        assert store.latest_step(d) == 3
        out, meta = store.restore(d, 3, tree)
        np.testing.assert_array_equal(out["a"], tree["a"])
        np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])
        assert out["b"]["d"] is None
        assert meta["loss"] == 1.5


def test_checkpoint_gc_keeps_last_k():
    tree = {"a": jnp.zeros((2,))}
    with tempfile.TemporaryDirectory() as d:
        for s in range(6):
            store.save(d, s, tree, keep_last=2)
        assert store.all_steps(d) == [4, 5]


def test_async_checkpointer():
    tree = {"a": jnp.arange(4.0)}
    with tempfile.TemporaryDirectory() as d:
        ck = store.AsyncCheckpointer(d, keep_last=3)
        ck.save(1, tree)
        ck.save(2, tree)       # waits for 1
        ck.wait()
        assert store.all_steps(d) == [1, 2]


def test_checkpoint_atomicity_no_tmp_left():
    tree = {"a": jnp.zeros((2,))}
    with tempfile.TemporaryDirectory() as d:
        store.save(d, 0, tree)
        assert not any(x.endswith(".tmp") for x in os.listdir(d))


# ---------------- optimizer ----------------
def _mesh11():
    from repro.core import compat
    return compat.make_mesh((1, 1), ("data", "model"))


def test_adamw_decreases_quadratic_loss():
    mesh = _mesh11()
    info = mesh_info(mesh)
    from jax.sharding import PartitionSpec as P
    specs = {"w": prm.Spec((8,), P(None), jnp.float32)}
    params = {"w": jnp.full((8,), 5.0)}
    opt = adamw.init_opt_state(params, specs, info)
    cfg = adamw.AdamWConfig(learning_rate=0.1, warmup_steps=0,
                            weight_decay=0.0)
    def loss(p):
        return jnp.sum(p["w"] ** 2)
    l0 = float(loss(params))
    for _ in range(50):
        g = jax.grad(loss)(params)
        params, opt, _ = adamw.apply_updates(params, g, opt, cfg)
    assert float(loss(params)) < 0.1 * l0


def test_grad_clip_bounds_update():
    mesh = _mesh11()
    info = mesh_info(mesh)
    from jax.sharding import PartitionSpec as P
    specs = {"w": prm.Spec((4,), P(None), jnp.float32)}
    params = {"w": jnp.zeros((4,))}
    opt = adamw.init_opt_state(params, specs, info)
    cfg = adamw.AdamWConfig(learning_rate=1.0, warmup_steps=0,
                            grad_clip=1.0, weight_decay=0.0)
    g = {"w": jnp.full((4,), 1e6)}
    _, _, gnorm = adamw.apply_updates(params, g, opt, cfg)
    assert float(gnorm) > 1e5          # reported norm is pre-clip


def test_int8_compression_error_feedback():
    g = jnp.linspace(-1, 1, 64)
    deq, err = adamw.compress_int8(g, None)
    assert float(jnp.max(jnp.abs(deq - g))) < 1.0 / 127 + 1e-6
    # error feedback: residual carries what quantization dropped
    np.testing.assert_allclose(deq + err, g, atol=1e-6)


def test_lr_schedule_warmup_and_decay():
    cfg = adamw.AdamWConfig(learning_rate=1.0, warmup_steps=10,
                            total_steps=100)
    lrs = [float(adamw.lr_at(cfg, jnp.int32(s))) for s in (1, 10, 50, 100)]
    assert lrs[0] < lrs[1]
    assert lrs[1] >= lrs[2] >= lrs[3]
    assert lrs[3] >= 0.05


# ---------------- straggler detector ----------------
def test_straggler_detector_flags_outlier():
    det = StragglerDetector()
    for i in range(20):
        assert not det.observe(i, 1.0 + 0.01 * (i % 3))
    assert det.observe(20, 10.0)
    assert det.slow_steps and det.slow_steps[0][0] == 20
