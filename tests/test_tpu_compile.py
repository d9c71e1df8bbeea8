"""Compile the Pallas kernels, and a TMP train step, for a described TPU
v5e at real model widths.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  These catch what interpret mode
cannot (tiling, fast-memory limits, how the compiler schedules the TMP
all-reduces) at no chip time.  The topology is described only inside the
module fixture, so importing this file under several test workers loads
nothing; the fixture skips where the topology cannot be described.
"""
import collections
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import collective_matmul as cm
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rms
from repro.kernels.autotune import DEFAULT_BLOCKS


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, sharding, *shapes):
    """Compile ``fn`` for the described chip; return the compiled HLO."""
    args = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
            for s in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    return compiled.as_text()


@pytest.mark.parametrize("b,s,h,kvh,hd", [
    (1, 1024, 16, 16, 64),      # gpt-h1024: MHA, head_dim 64
    (1, 1024, 16, 8, 128),      # internlm2-1.8b: GQA 16/8, head_dim 128
], ids=["gpt-h1024", "internlm2-1.8b"])
def test_flash_attention_compiles_for_v5e(one_chip, b, s, h, kvh, hd):
    fn = functools.partial(fa.flash_attention, causal=True, interpret=False)
    hlo = _compile(fn, one_chip, (b, s, h, hd), (b, s, kvh, hd),
                   (b, s, kvh, hd))
    assert "tpu_custom_call" in hlo


def test_rmsnorm_compiles_for_v5e(one_chip):
    # internlm2-1.8b / gpt-h2048 width, one 1024-token sequence
    fn = functools.partial(rms.rmsnorm, interpret=False)
    hlo = _compile(fn, one_chip, (1024, 2048), (2048,))
    assert "tpu_custom_call" in hlo


def test_tile_matmul_compiles_for_v5e(one_chip):
    # gpt-h2048 under TMP 4: one chip's column shard of the MLP up
    # projection, [batch 8 x seq 1024, 2048] @ [2048, 8192 / 4]
    bm, bn, bk = DEFAULT_BLOCKS
    fn = functools.partial(cm.pallas_tile_matmul, block_m=bm, block_n=bn,
                           block_k=bk, interpret=False)
    hlo = _compile(fn, one_chip, (8192, 2048), (2048, 2048))
    assert "tpu_custom_call" in hlo


# --------------------------------------------------------------------------
# the TMP train step: gpt-h2048's widths, 2 layers, TMP 4 on the 2x2 host
# --------------------------------------------------------------------------
SUB_BATCH = "bf16[8,1024,2048]"     # one sub-batch's row-matmul output


def _trainer(topo, tmp_path, model: int, data: int = 1):
    """The Trainer on a ``data`` x ``model`` mesh of described chips (global
    batch 16 of 1024 tokens, ``oases``)."""
    from repro.configs.base import TrainHParams
    from repro.configs.registry import get_config
    from repro.core import compat
    from repro.runtime import Trainer

    cfg = get_config("gpt-h2048").replace(num_layers=2, tie_embeddings=False)
    mesh = compat.make_mesh((data, model), ("data", "model"),
                            devices=topo.devices[:data * model])
    return Trainer(cfg, mesh, TrainHParams(schedule="oases"),
                   global_batch=16, seq_len=1024, ckpt_dir=str(tmp_path),
                   log_fn=lambda *_: None)


def _computations(hlo: str) -> dict:
    comps, name = {}, None
    for line in hlo.splitlines():
        if line and not line[0].isspace() and line.rstrip().endswith("{"):
            name = line.split()[1 if line.startswith("ENTRY") else 0]
            comps[name.lstrip("%")] = []
        elif name:
            comps[name.lstrip("%")].append(line)
    return comps


def _compiled(tr, jitted=None) -> str:
    """The compiled text of the Trainer's step, or of ``jitted`` over the
    same inputs."""
    from repro.launch import steps

    args = steps.train_abstract_inputs(tr.cfg, tr.mesh, tr.hp,
                                       global_batch=16, seq_len=1024,
                                       plan=tr.plan)
    return (jitted or tr.step_fn).lower(*args).compile().as_text()


def test_tmp4_step_overlaps_sub_batch_all_reduces(topo, tmp_path):
    hlo = _compiled(_trainer(topo, tmp_path, 4))

    # no all-reduce carries two sub-batches' reductions
    for line in hlo.splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\(", line)
        if m:
            assert m.group(1).count(SUB_BATCH) < 2, line

    # an asynchronous all-reduce in the layer loop of each pass
    comps = _computations(hlo)
    passes = collections.Counter()
    for called in re.findall(
            r"async-collective-start(?:\.\d+)? = .*?calls=%([\w.\-]+)", hlo):
        m = re.search(r'all-reduce\(.*op_name="([^"]*)"',
                      "\n".join(comps[called]))
        if m and "/while/body/" in m.group(1):
            passes["bwd" if "transpose(" in m.group(1) else "fwd"] += 1
    assert passes["fwd"] >= 1 and passes["bwd"] >= 1, passes


def test_one_chip_step_takes_no_compile_options(topo, tmp_path):
    from repro.launch import steps

    tr = _trainer(topo, tmp_path, 1)
    assert steps.train_compiler_options(tr.mesh) is None


_GROUPS_RE = re.compile(r"replica_groups=(\{\{[\d,{}]*\}\}|\[[^ ]*)")


def _non_tmp_all_reduces(hlo: str) -> collections.Counter:
    """(result shape, replica groups) of every all-reduce outside the TMP
    model groups: the data-parallel gradients and the loss's scalars.  The
    shapes drop their layouts, where only the memory space differs."""
    rows = []
    for line in hlo.splitlines():
        m = re.search(r"= (.*?) all-reduce(?:-start)?\(", line)
        if m:
            rows.append((re.sub(r"\{[^{}]*\}", "", m.group(1)),
                         _GROUPS_RE.search(line).group(1),
                         "tmp.reduce" in line))
    tmp_groups = {g for _, g, tmp in rows if tmp}
    return collections.Counter((shape, g) for shape, g, _ in rows
                               if g not in tmp_groups)


def test_async_options_leave_data_parallel_reductions_alone(topo, tmp_path):
    """On a data 2 x model 2 mesh the options reach every all-reduce of the
    step, the gradients' over the data axis too: those compile as they do
    without the options (the 1 MiB combiner cap merges none of them)."""
    import jax

    tr = _trainer(topo, tmp_path, 2, data=2)
    plain = jax.jit(tr.step_fn.__wrapped__, donate_argnums=(0, 1))
    ours, base = (_non_tmp_all_reduces(_compiled(tr, j))
                  for j in (None, plain))
    assert sum(ours.values()) >= 2
    assert ours == base
