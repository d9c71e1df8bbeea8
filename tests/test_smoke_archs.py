"""Required per-arch smoke tests: REDUCED config, one forward/train step on
CPU, assert output shapes + no NaNs.  (Full configs are exercised only via
the dry-run.)"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs.base import TrainHParams
from repro.configs.registry import ASSIGNED, get_config
from repro.models import lm
from repro.models import params as prm


def _batch(cfg, b=2, s=32):
    k = jax.random.PRNGKey(7)
    out = {
        "tokens": jax.random.randint(k, (b, s), 0, cfg.vocab_size, jnp.int32),
        "labels": jax.random.randint(k, (b, s), 0, cfg.vocab_size, jnp.int32),
    }
    if cfg.context_len:
        out["ctx"] = 0.02 * jax.random.normal(
            k, (b, cfg.context_len, cfg.d_model), jnp.float32)
    return out


@pytest.mark.parametrize("arch", ASSIGNED)
def test_train_step_smoke(arch, smoke_mesh):
    cfg = get_config(arch).reduced().replace(dtype="float32")
    hp = TrainHParams(schedule="oases", fine_remat=True)
    loss_fn, specs, _ = lm.build_train_loss(cfg, smoke_mesh, hp,
                                            global_batch=2, seq_len=32)
    params = prm.init_params(specs, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    with jax.set_mesh(smoke_mesh):
        (loss, aux), grads = jax.jit(
            jax.value_and_grad(loss_fn, has_aux=True))(params, batch)
    assert loss.shape == ()
    assert not bool(jnp.isnan(loss)), arch
    gn = sum(float(jnp.sum(jnp.abs(g)))
             for g in jax.tree_util.tree_leaves(grads))
    assert gn > 0 and not jnp.isnan(gn)


@pytest.mark.parametrize("arch", ASSIGNED)
def test_prefill_decode_smoke(arch, smoke_mesh):
    cfg = get_config(arch).reduced().replace(dtype="float32")
    hp = TrainHParams()
    b, s = 2, 32
    pf, specs, st_specs = lm.build_prefill(cfg, smoke_mesh, hp,
                                           global_batch=b, seq_len=s)
    df, _, _ = lm.build_decode(cfg, smoke_mesh, hp, global_batch=b,
                               seq_len=s)
    params = prm.init_params(specs, jax.random.PRNGKey(0))
    batch = {k: v for k, v in _batch(cfg, b, s).items() if k != "labels"}
    with jax.set_mesh(smoke_mesh):
        tok, state = jax.jit(pf)(params, batch)
        tok2, state2 = jax.jit(df)(params, state, tok,
                                   jnp.full((b,), s - 1, jnp.int32))
    assert tok.shape == (b,) and tok2.shape == (b,)
    assert int(tok.max()) < cfg.padded_vocab()
    assert (jax.tree_util.tree_structure(state)
            == jax.tree_util.tree_structure(state2))
    for l1, l2 in zip(jax.tree_util.tree_leaves(state),
                      jax.tree_util.tree_leaves(state2)):
        assert l1.shape == l2.shape
        assert not bool(jnp.any(jnp.isnan(l2.astype(jnp.float32))))
