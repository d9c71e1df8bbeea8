"""Compile the Pallas kernels for a described TPU v5e at real model widths.

No chip is needed: the TPU compiler is installed and compiles for a
topology that is described, not attached.  These catch what interpret mode
cannot (tiling, fast-memory limits) at no chip time.  The topology is
described only inside the module fixture, so importing this file under
several test workers loads nothing; the fixture skips where the topology
cannot be described.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import collective_matmul as cm
from repro.kernels import flash_attention as fa
from repro.kernels import rmsnorm as rms
from repro.kernels.autotune import DEFAULT_BLOCKS


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
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
