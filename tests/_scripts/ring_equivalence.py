"""Subprocess body: ring attention (DESIGN.md §12) vs the 1-device
oracle.  PASS/FAIL lines consumed by test_distributed.

Two tiers in one subprocess (the 8-virtual-device topology is expensive
to boot, so both ride the same interpreter):

* kernel tier — ``kernels.ring_attention`` under an 8-way shard_map vs
  ``models.attention.chunked_attention``, forward AND grads (the custom
  VJP's reverse ring), fp32 + bf16, causal / sliding-window / GQA /
  softcap, and uneven sequence tiles (padded rows at kv position -1);
* model tier — full train loss+grads through ``lm.build_train_loss``:
  the stacked ring path (uniform ``seq_shard``), the grouped path with
  mixed per-layer seqs, and gemma2 (GQA + softcap + local attention);
  plus the hard-error paths (bad shard factor, indivisible seq_len).
"""
import runner  # noqa: F401  (must be first: sets XLA_FLAGS before jax)

import dataclasses

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import TrainHParams
from repro.kernels.ring_attention import ring_attention
from repro.models.attention import chunked_attention

# ---------------------------------------------------------------------------
# kernel tier
# ---------------------------------------------------------------------------
kmesh = runner.mesh(runner.N_DEVICES)


def kernel_case(name, *, b=2, s=64, h=4, kvh=4, hd=16, causal=True,
                window=None, softcap=0.0, dtype=jnp.float32, pad=0):
    key = jax.random.PRNGKey(0)
    kq, kk, kv, kd = jax.random.split(key, 4)
    q = jax.random.normal(kq, (b, s, h, hd), dtype)
    k = jax.random.normal(kk, (b, s, kvh, hd), dtype)
    v = jax.random.normal(kv, (b, s, kvh, hd), dtype)
    do = jax.random.normal(kd, (b, s, h, hd), dtype)
    pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None], (b, s))
    if pad:
        # uneven tiles: the last `pad` rows are padding (kv position -1;
        # their q rows leave the loss via a zero cotangent)
        pos = pos.at[:, s - pad:].set(-1)
        do = do.at[:, s - pad:].set(0.0)

    def loss_ref(q, k, v):
        o = chunked_attention(q, k, v, causal=causal, window=window,
                              softcap=softcap, q_positions=pos,
                              kv_positions=pos)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

    def ring_body(q, k, v, qp, kvp):
        return ring_attention(q, k, v, axes=("model",), causal=causal,
                              window=window, softcap=softcap,
                              q_positions=qp, kv_positions=kvp)

    smap = jax.shard_map(ring_body, mesh=kmesh,
                         in_specs=(P(None, "model"), P(None, "model"),
                                   P(None, "model"), P(None, "model"),
                                   P(None, "model")),
                         out_specs=P(None, "model"), check_vma=False)

    def loss_ring(q, k, v):
        o = smap(q, k, v, pos, pos)
        return jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32)), o

    (_, o_ref), g_ref = jax.value_and_grad(loss_ref, argnums=(0, 1, 2),
                                           has_aux=True)(q, k, v)
    (_, o_ring), g_ring = jax.value_and_grad(loss_ring, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    errs = []
    # fully-masked rows (padding) carry unspecified values in both
    # implementations — mask them out of the comparison
    live = (pos >= 0)[:, :, None, None]
    errs.append(("out", float(jnp.max(jnp.abs(
        jnp.where(live, o_ref, 0).astype(jnp.float32)
        - jnp.where(live, o_ring, 0).astype(jnp.float32))))))
    for nm, a, bb in zip("qkv", g_ref, g_ring):
        errs.append((f"d{nm}", float(
            jnp.max(jnp.abs(a.astype(jnp.float32)
                            - bb.astype(jnp.float32)))
            / (float(jnp.max(jnp.abs(a))) + 1e-6))))
    runner.report(f"kernel-{name}", all(e < tol for _, e in errs),
                  " ".join(f"{nm}={e:.2e}" for nm, e in errs))


kernel_case("causal-fp32")
kernel_case("noncausal-fp32", causal=False)
kernel_case("window-fp32", window=24)
kernel_case("window-subblock-fp32", window=4)
kernel_case("gqa-fp32", h=8, kvh=2)
kernel_case("softcap-gqa-fp32", h=8, kvh=2, softcap=30.0)
kernel_case("causal-bf16", dtype=jnp.bfloat16)
kernel_case("gqa-window-bf16", h=8, kvh=2, window=24, dtype=jnp.bfloat16)
kernel_case("uneven-pad-fp32", pad=5)
kernel_case("uneven-pad-window-fp32", pad=13, window=24)

# ---------------------------------------------------------------------------
# model tier
# ---------------------------------------------------------------------------
hp0 = TrainHParams()
msh1 = runner.mesh(1, 1)
msh = runner.mesh(1, runner.N_DEVICES)
hp_ring = dataclasses.replace(hp0, seq_shard=runner.N_DEVICES,
                              seq_parallel=True)

l_ref, g_ref = runner.train_loss_and_grads("internlm2-1.8b", msh1, hp0)

# stacked ring: uniform seq_shard over the model axis (implied SP)
l_ring, g_ring = runner.train_loss_and_grads("internlm2-1.8b", msh, hp_ring)
runner.report("model-ring-stacked-loss", abs(l_ref - l_ring) < 2e-4,
              f"dloss={abs(l_ref - l_ring):.2e}")
runner.check("model-ring-stacked-grads", g_ring, g_ref, 5e-3)

# seq_shard alone must imply the sequence-parallel activation layout
l_r2, _ = runner.train_loss_and_grads(
    "internlm2-1.8b", msh,
    dataclasses.replace(hp0, seq_shard=runner.N_DEVICES))
runner.report("model-ring-implied-sp-loss", abs(l_ref - l_r2) < 2e-4,
              f"dloss={abs(l_ref - l_r2):.2e}")

# grouped path: mixed per-layer seqs (half ring, half classic)
cfg = runner.reduced_config("internlm2-1.8b")
n = cfg.num_layers
seqs = [runner.N_DEVICES] * (n // 2) + [1] * (n - n // 2)
l_mix, g_mix = runner.train_loss_and_grads(
    "internlm2-1.8b", msh, hp0, seqs=seqs, canonical_init=True)
g_mix = runner.canonical_grads("internlm2-1.8b", g_mix, seqs=seqs, hp=hp0)
runner.report("model-ring-mixed-loss", abs(l_ref - l_mix) < 2e-4,
              f"dloss={abs(l_ref - l_mix):.2e}")
runner.check("model-ring-mixed-grads", g_mix, g_ref, 5e-3)

# gemma2: GQA + softcap + alternating local/global attention + post-norms
l_g_ref, g_g_ref = runner.train_loss_and_grads("gemma2-9b", msh1, hp0)
l_g, g_g = runner.train_loss_and_grads("gemma2-9b", msh, hp_ring)
runner.report("model-ring-gemma2-loss", abs(l_g_ref - l_g) < 2e-4,
              f"dloss={abs(l_g_ref - l_g):.2e}")
runner.check("model-ring-gemma2-grads", g_g, g_g_ref, 5e-3)

# error paths: an unsatisfiable seq_shard is a hard error, never a
# silent fallback (cf. models/lm.py ring_blockers)
try:
    runner.train_loss_and_grads(
        "internlm2-1.8b", msh,
        dataclasses.replace(hp0, seq_shard=max(runner.N_DEVICES // 2, 2)))
    runner.report("model-ring-bad-shard-raises", False, "no error")
except ValueError as e:
    runner.report("model-ring-bad-shard-raises", "seq_shard" in str(e))
try:
    runner.train_loss_and_grads(
        "internlm2-1.8b", msh,
        dataclasses.replace(hp0, seq_shard=runner.N_DEVICES),
        seq=runner.N_DEVICES * 8 - 4)
    runner.report("model-ring-bad-seqlen-raises", False, "no error")
except ValueError as e:
    runner.report("model-ring-bad-seqlen-raises", "divisible" in str(e))

import sys
sys.exit(runner.exit_code())
