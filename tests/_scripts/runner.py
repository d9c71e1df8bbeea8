"""Shared harness for the multi-device subprocess scripts.

Every script in this directory runs as ``python tests/_scripts/<name>.py``
inside a fresh process and talks to its parent test through PASS/FAIL
lines on stdout.  This module centralizes the boilerplate they used to
re-implement: the virtual-device environment (which MUST be configured
before the first jax import — hence ``import runner`` is each script's
first statement), mesh construction, reduced test configs, loss/grad
evaluation under ``shard_map``, and the PASS/FAIL reporting protocol.

Usage:

    import runner                      # sets XLA_FLAGS, first import
    loss, grads = runner.train_loss_and_grads("gemma2-9b", runner.mesh(2, 4))
    runner.check("my-case", grads, ref_grads, tol=5e-3)
"""
import os

N_DEVICES = int(os.environ.get("OASES_TEST_DEVICES", "8"))
os.environ["XLA_FLAGS"] = (
    f"--xla_force_host_platform_device_count={N_DEVICES}")
# ^ before any jax import: jax locks the device count on first init.

import dataclasses  # noqa: E402

import jax          # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import compat                      # noqa: E402
from repro.configs.base import TrainHParams        # noqa: E402
from repro.configs.registry import get_config      # noqa: E402
from repro.models import lm                        # noqa: E402
from repro.models import params as prm             # noqa: E402

_FAILED = [0]


# --------------------------------------------------------------------------
# environment / mesh
# --------------------------------------------------------------------------
def mesh(*shape, axes=None):
    """Mesh over the virtual devices; default axis names by rank:
    1 -> ('model',), 2 -> ('data','model'), 3 -> ('data','model_x','model_y')."""
    if axes is None:
        axes = {1: ("model",), 2: ("data", "model"),
                3: ("data", "model_x", "model_y")}[len(shape)]
    return compat.make_mesh(shape, axes)


def factored_mesh(data=1, t=(2, 2, 2)):
    names = ("data",) + tuple(f"t{i+1}" for i in range(len(t)))
    return compat.make_mesh((data,) + tuple(t), names)


def reduced_config(arch: str, *, exact_moe: bool = True):
    """The tiny same-family fp32 config every equivalence script uses.
    ``exact_moe``: no-drop routing + zero aux weight so MoE losses are
    bitwise comparable across meshes."""
    cfg = get_config(arch).reduced().replace(dtype="float32")
    if exact_moe and cfg.moe is not None:
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=100.0, router_aux_weight=0.0))
    return cfg


def make_batch(cfg, batch: int, seq: int, seed: int = 42):
    k = jax.random.PRNGKey(seed)
    out = {"tokens": jax.random.randint(k, (batch, seq), 0, cfg.vocab_size,
                                        jnp.int32),
           "labels": jax.random.randint(k, (batch, seq), 0, cfg.vocab_size,
                                        jnp.int32)}
    if cfg.context_len:
        out["ctx"] = 0.02 * jax.random.normal(
            k, (batch, cfg.context_len, cfg.d_model), jnp.float32)
    return out


# --------------------------------------------------------------------------
# loss/grad evaluation
# --------------------------------------------------------------------------
def flatten(tree):
    return {jax.tree_util.keystr(kp): np.asarray(jax.device_get(v))
            for kp, v in jax.tree_util.tree_flatten_with_path(tree)[0]}


def train_loss_and_grads(arch_or_cfg, msh, hp: TrainHParams = None, *,
                         batch: int = 4, seq: int = 64, degrees=None,
                         schedules=None, seqs=None, seed: int = 0,
                         batch_seed: int = 42,
                         canonical_init: bool = False):
    """(loss, flat-grad dict) of the reduced config on a mesh — the body
    every per-feature script used to duplicate.

    ``canonical_init``: initialize parameters in the canonical STACKED
    layout and relayout into the run's grouped (planner-mode) layout, so
    a per-layer-plan run is value-comparable against the 1-device oracle
    (grouped spec trees flatten in a different order, which would
    otherwise deal different RNG keys per leaf).  Pair with
    :func:`canonical_grads` on the result."""
    cfg = (reduced_config(arch_or_cfg) if isinstance(arch_or_cfg, str)
           else arch_or_cfg)
    hp = hp or TrainHParams()
    loss_fn, specs, _ = lm.build_train_loss(
        cfg, msh, hp, global_batch=batch, seq_len=seq, degrees=degrees,
        schedules=schedules, seqs=seqs)
    if canonical_init and (degrees is not None or schedules is not None
                           or seqs is not None):
        from repro.core.axes import mesh_info
        base_specs = prm.model_specs(cfg, mesh_info(msh), max_pos=seq,
                                     layout=hp.tmp_layout)
        p0 = prm.init_params(base_specs, jax.random.PRNGKey(seed))
        flat = prm.relayout_flat(
            cfg, prm.tree_to_flat(p0), {},
            _layout_meta(cfg, degrees, schedules, hp, seqs))
        p = prm.tree_from_flat(specs, flat)
    else:
        p = prm.init_params(specs, jax.random.PRNGKey(seed))
    b = make_batch(cfg, batch, seq, batch_seed)
    with jax.set_mesh(msh):
        loss = float(jax.jit(loss_fn)(p, b)[0])
        grads = jax.jit(jax.grad(lambda p, b: loss_fn(p, b)[0]))(p, b)
    return loss, flatten(grads)


def _layout_meta(cfg, degrees, schedules, hp, seqs=None):
    """The relayout descriptor of a (degrees, schedules, seqs) run —
    mirrors lm._normalize_strategy's grouping promotion.  A uniform
    seq_shard on the stacked layout keeps the stacked flat keys, so it
    needs no relayout; only mixed seqs force the grouped layout."""
    seq_uniform = 1
    if seqs is not None and len(set(seqs)) == 1:
        seq_uniform, seqs = seqs[0], None
    if schedules is not None and len(set(schedules)) == 1:
        schedules = None
    if degrees is None and schedules is None and seqs is None:
        return {}
    degs = list(degrees) if degrees is not None \
        else [None] * cfg.num_layers
    scheds = (list(schedules) if schedules is not None
              else [hp.schedule] * cfg.num_layers)
    meta = {"degrees": degs, "schedules": scheds}
    seq_all = seq_uniform if seq_uniform > 1 \
        else getattr(hp, "seq_shard", 1)
    if seqs is not None:
        meta["seqs"] = list(seqs)
    elif seq_all > 1:
        meta["seqs"] = [seq_all] * cfg.num_layers
    return meta


def canonical_grads(arch_or_cfg, g: dict, *, degrees=None, schedules=None,
                    seqs=None, hp: TrainHParams = None) -> dict:
    """Relayout a grouped run's flat grad dict back into the canonical
    stacked layout for oracle comparison."""
    cfg = (reduced_config(arch_or_cfg) if isinstance(arch_or_cfg, str)
           else arch_or_cfg)
    meta = _layout_meta(cfg, degrees, schedules, hp or TrainHParams(),
                        seqs)
    return prm.relayout_flat(cfg, g, meta, {}) if meta else g


# --------------------------------------------------------------------------
# reporting (consumed by tests/test_distributed.py etc.)
# --------------------------------------------------------------------------
def rel_err(a, b) -> float:
    a = [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(a)]
    b = [np.asarray(x, np.float32) for x in jax.tree_util.tree_leaves(b)]
    return max(float(np.max(np.abs(x - y)))
               / (float(np.max(np.abs(x))) + 1e-6)
               for x, y in zip(a, b))


def grads_err(g1: dict, g2: dict) -> float:
    return max(float(np.max(np.abs(g1[k] - g2[k])))
               / (float(np.max(np.abs(g1[k]))) + 1e-8) for k in g1)


def match_shapes(g: dict, ref: dict) -> dict:
    """Reshape a flat grad dict onto a reference layout.  Pipeline meshes
    stack layer groups [v, pp, n/S, ...] whose row-major flatten is the
    canonical [n, ...] order, so comparing against the single-device
    oracle is a pure reshape per leaf."""
    return {k: v.reshape(ref[k].shape) for k, v in g.items()}


def report(name: str, ok: bool, detail: str = ""):
    _FAILED[0] += 0 if ok else 1
    print(f"{'PASS' if ok else 'FAIL'} {name}"
          + (f" {detail}" if detail else ""), flush=True)
    return ok


def check(name: str, a, b, tol: float):
    err = rel_err(a, b)
    return report(name, err < tol, f"err={err:.2e}")


def check_close(name: str, x: float, y: float, tol: float):
    return report(name, abs(x - y) < tol, f"diff={abs(x - y):.2e}")


def exit_code() -> int:
    """Optional strict exit: scripts may end with sys.exit(runner.exit_code())
    (the parent asserts on FAIL lines either way)."""
    return 1 if _FAILED[0] else 0
