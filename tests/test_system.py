"""End-to-end behaviour tests for the whole system (single-device mesh)."""
import jax

from repro.configs.base import TrainHParams
from repro.configs.registry import get_config
from repro.launch import steps as steps_mod
from repro.models import params as prm
from repro.optim import adamw
from repro.core.axes import mesh_info


def test_train_step_improves_loss_on_fixed_batch(smoke_mesh):
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    hp = TrainHParams(learning_rate=3e-3, warmup_steps=1, total_steps=50)
    fn, specs = steps_mod.build_train_step(cfg, smoke_mesh, hp,
                                           global_batch=2, seq_len=32)
    info = mesh_info(smoke_mesh)
    params = prm.init_params(specs, jax.random.PRNGKey(0))
    opt = adamw.init_opt_state(params, specs, info)
    k = jax.random.PRNGKey(1)
    batch = {"tokens": jax.random.randint(k, (2, 32), 0, cfg.vocab_size),
             "labels": jax.random.randint(k, (2, 32), 0, cfg.vocab_size)}
    step = jax.jit(fn)
    with jax.set_mesh(smoke_mesh):
        losses = []
        for _ in range(12):
            params, opt, m = step(params, opt, batch)
            losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.5, losses


def test_microbatched_step_matches_full_batch(smoke_mesh):
    """Gradient accumulation must not change the loss value."""
    cfg = get_config("internlm2-1.8b").reduced().replace(dtype="float32")
    k = jax.random.PRNGKey(1)
    tokens = jax.random.randint(k, (4, 32), 0, cfg.vocab_size)
    labels = jax.random.randint(k, (4, 32), 0, cfg.vocab_size)

    def one(hp, batch):
        fn, specs = steps_mod.build_train_step(cfg, smoke_mesh, hp,
                                               global_batch=4, seq_len=32)
        info = mesh_info(smoke_mesh)
        params = prm.init_params(specs, jax.random.PRNGKey(0))
        opt = adamw.init_opt_state(params, specs, info)
        with jax.set_mesh(smoke_mesh):
            _, _, m = jax.jit(fn)(params, opt, batch)
        return float(m["loss"])

    l_full = one(TrainHParams(microbatch=1),
                 {"tokens": tokens, "labels": labels})
    l_micro = one(TrainHParams(microbatch=2),
                  {"tokens": tokens.reshape(2, 2, 32),
                   "labels": labels.reshape(2, 2, 32)})
    assert abs(l_full - l_micro) < 1e-4


def test_input_specs_cover_all_cells(smoke_mesh):
    """input_specs() must produce valid abstract inputs for every
    applicable (arch x shape) cell without allocating."""
    from repro.configs.registry import all_cells
    for cfg, shape, applicable in all_cells():
        if not applicable:
            continue
        got = steps_mod.input_specs(cfg, shape, smoke_mesh, TrainHParams())
        leaves = jax.tree_util.tree_leaves(got)
        assert all(isinstance(leaf, jax.ShapeDtypeStruct) for leaf in leaves)
