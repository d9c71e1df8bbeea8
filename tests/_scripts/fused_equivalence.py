"""Subprocess body: fused collective-matmul numerics + schedule equivalence.

Part 1 — kernel-level: the ring decompositions (matmul→AR, matmul→RS,
AG→matmul) must match the jnp.dot + lax.psum/psum_scatter/all_gather
oracles in forward AND gradient, for fp32 and bf16 and for uneven
(non-power-of-two chunk) tile shapes, on an 8-virtual-device mesh.

Part 2 — edge cases the suite used to skip: a scatter/gather dim the ring
degree does NOT divide (AR falls back to the blocking reference, RS raises
the explicit divisibility error), degree=1 degeneracy on a real size-1
mesh axis, and bf16 gradient tolerance through the fused rings.

Part 3 — schedule-level: ``schedule="fused"`` must match ``megatron``
loss/grads bitwise-tolerantly under a 2-device model mesh (and the SP
variant under a 4-way axis, the only mode reaching the custom-VJP pair).

Prints PASS/FAIL lines consumed by tests/test_collective_matmul.py.
"""
import runner  # noqa: F401  (must be first: sets XLA_FLAGS before jax)

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import TrainHParams
from repro.kernels import collective_matmul as cm

AXES = ("model",)


def _tol(dtype):
    return 3e-2 if dtype == jnp.bfloat16 else 2e-5


def pair(mesh, fused_body, ref_body, in_specs, out_specs, args):
    """((fused_out, fused_grads), (ref_out, ref_grads)) under shard_map."""
    smf = jax.shard_map(fused_body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)
    smr = jax.shard_map(ref_body, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs, check_vma=False)

    def loss(f):
        return lambda *a: sum(
            jnp.sum(jnp.tanh(o.astype(jnp.float32)))
            for o in jax.tree_util.tree_leaves(f(*a)))

    of, orf = jax.jit(smf)(*args), jax.jit(smr)(*args)
    gf = jax.jit(jax.grad(loss(smf), argnums=tuple(range(len(args)))))(*args)
    gr = jax.jit(jax.grad(loss(smr), argnums=tuple(range(len(args)))))(*args)
    return (of, gf), (orf, gr)


def kernel_level(dtype, b, s, k, d, mesh=None, axes=AXES, tag_extra=""):
    mesh = mesh or runner.mesh(8, axes=("model",))
    kx, kw, kw2 = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(kx, (b, s, k), dtype)
    w = (0.1 * jax.random.normal(kw, (k, d))).astype(dtype)
    w2 = (0.1 * jax.random.normal(kw2, (k, d))).astype(dtype)
    tag = f"{dtype.__name__}-{b}x{s}x{k}x{d}{tag_extra}"
    nm = dict(mesh.shape).get("model", 1)
    kspec = "model" if nm > 1 else None

    # matmul -> all-reduce (row-parallel exit, K sharded)
    f, r = pair(
        mesh,
        lambda xl, wl: cm.fused_matmul_allreduce(xl, wl, axes),
        lambda xl, wl: cm.matmul_allreduce_ref(xl, wl, axes),
        (P(None, None, kspec), P(kspec, None)), P(), (x, w))
    runner.check(f"ar-{tag}", f, r, _tol(dtype))

    if s % max(nm, 1) == 0:
        # matmul -> reduce-scatter (SP exit, scatter along seq)
        f, r = pair(
            mesh,
            lambda xl, wl: cm.fused_matmul_reducescatter(xl, wl, axes, 1),
            lambda xl, wl: cm.matmul_reducescatter_ref(xl, wl, axes, 1),
            (P(None, None, kspec), P(kspec, None)),
            P(None, kspec, None), (x, w))
        runner.check(f"rs-{tag}", f, r, _tol(dtype))

        # all-gather -> matmul, two weights on one ring (SP entry)
        f, r = pair(
            mesh,
            lambda xl, w1, w2: cm.fused_allgather_matmul(xl, (w1, w2),
                                                         axes, 1),
            lambda xl, w1, w2: cm.allgather_matmul_ref(xl, (w1, w2),
                                                       axes, 1),
            (P(None, kspec, None), P(None, kspec), P(None, kspec)),
            (P(None, None, kspec), P(None, None, kspec)), (x, w, w2))
        runner.check(f"ag-{tag}", f, r, _tol(dtype))


# ---- part 1: ring-vs-oracle fwd+grad, fp32/bf16, uneven tiles ------------
for dtype in (jnp.float32, jnp.bfloat16):
    kernel_level(dtype, 2, 32, 64, 48)
kernel_level(jnp.float32, 1, 24, 40, 56)       # uneven: chunks of 3 rows
kernel_level(jnp.float32, 3, 16, 104, 72)      # uneven K_local=13
# bf16 gradient tolerance through the ring on uneven tiles
kernel_level(jnp.bfloat16, 1, 24, 40, 56, tag_extra="-uneven")

# ---- part 2: edge cases ---------------------------------------------------
# (a) scatter dim NOT divisible by the ring degree: the AR flavour must
# fall back to the blocking reference and stay exact (s=30, n=8)
kernel_level(jnp.float32, 2, 30, 64, 48, tag_extra="-nodiv")

# (b) reduce-scatter semantics genuinely need divisibility: explicit error
mesh8 = runner.mesh(8, axes=("model",))
x30 = jax.random.normal(jax.random.PRNGKey(1), (2, 30, 64))
w64 = 0.1 * jax.random.normal(jax.random.PRNGKey(2), (64, 48))
try:
    sm = jax.shard_map(
        lambda xl, wl: cm.fused_matmul_reducescatter(xl, wl, AXES, 1),
        mesh=mesh8, in_specs=(P(None, None, "model"), P("model", None)),
        out_specs=P(None, "model", None), check_vma=False)
    jax.jit(sm)(x30, w64)
    runner.report("rs-nodiv-raises", False, "no error raised")
except ValueError as e:
    runner.report("rs-nodiv-raises", "not divisible" in str(e), str(e)[:60])

# (c) degree=1 degeneracy: a real size-1 model axis must degrade to the
# plain dot (backend 'ref'), forward and gradient
mesh1 = runner.mesh(8, 1)
xb = jax.random.normal(jax.random.PRNGKey(3), (8, 16, 32))
wb = 0.1 * jax.random.normal(jax.random.PRNGKey(4), (32, 24))
f, r = pair(
    mesh1,
    lambda xl, wl: cm.fused_matmul_allreduce(xl, wl, AXES),
    lambda xl, wl: jnp.dot(xl, wl),
    (P("data", None, None), P(None, None)), P("data", None, None),
    (xb, wb))
runner.check("ar-degree1", f, r, 2e-5)
f, r = pair(
    mesh1,
    lambda xl, wl: cm.fused_matmul_reducescatter(xl, wl, AXES, 1),
    lambda xl, wl: jnp.dot(xl, wl),
    (P("data", None, None), P(None, None)), P("data", None, None),
    (xb, wb))
runner.check("rs-degree1", f, r, 2e-5)

# ---- part 3: schedule equivalence ----------------------------------------
def run(schedule, mesh, sp=False):
    hp = TrainHParams(schedule=schedule, fine_remat=True, seq_parallel=sp)
    return runner.train_loss_and_grads("internlm2-1.8b", mesh, hp)


mesh2 = runner.mesh(1, 2)
l_meg, g_meg = run("megatron", mesh2)
l_fus, g_fus = run("fused", mesh2)
runner.report("sched-loss", abs(l_meg - l_fus) < 1e-6,
              f"dloss={abs(l_meg - l_fus):.2e}")
runner.check("sched-grads", g_meg, g_fus, 5e-4)

# fused + sequence-parallel: the only mode reaching the custom-VJP pair
# (fused_allgather_matmul / fused_matmul_reducescatter) through the model,
# on a 4-way model axis so the rings actually run
mesh4 = runner.mesh(2, 4)
l_meg_sp, g_meg_sp = run("megatron", mesh4, sp=True)
l_fus_sp, g_fus_sp = run("fused", mesh4, sp=True)
runner.report("sched-sp-loss", abs(l_meg_sp - l_fus_sp) < 1e-6,
              f"dloss={abs(l_meg_sp - l_fus_sp):.2e}")
runner.check("sched-sp-grads", g_meg_sp, g_fus_sp, 5e-4)
