"""Plain reference of the dense decoder the configurations describe.

Pre-norm decoder: RMSNorm with a ``(1 + gain)`` scale, rotary positions
(half-split), causal grouped-query attention scaled by ``head_dim ** -0.5``,
a gated SiLU MLP, a final norm and an untied output head over the padded
vocabulary; loss is the mean next-token cross entropy over every position.
Training follows AdamW with mixed precision as the configuration states it:
bf16 weights made from an f32 master copy, global-norm clipping, bias-
corrected moments, linear warm-up then cosine decay to 10%.

Written from the configuration alone, in ``jax.numpy``: it imports nothing
of the program and takes nothing it made; weights come from the seed
through ``bench/weights.py``, in the layout the program's tree names.

``prec`` is ``"f32"`` (float32, every matmul at ``HIGHEST``) or ``"fp8"``,
the control: each matmul's operands rounded to an 8-bit float (e4m3) with a
scale per tensor (gradients pass the rounding straight through).
"""
from __future__ import annotations

import math
import sys
from pathlib import Path
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import weights as W  # noqa: E402

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


# --------------------------------------------------------------------------
# weights
# --------------------------------------------------------------------------
def shapes(model: dict) -> Dict:
    """Leaf path -> (shape, dtype) of the program's parameter tree."""
    L, d, f = model["num_layers"], model["d_model"], model["d_ff"]
    hq = model["num_heads"] * model["head_dim"]
    hk = model["num_kv_heads"] * model["head_dim"]
    vp = model["padded_vocab"]
    bf = jnp.bfloat16
    layer = {"ln": ((L, d), F32), "wq": ((L, d, hq), bf),
             "wk": ((L, d, hk), bf), "wv": ((L, d, hk), bf),
             "wo": ((L, hq, d), bf), "ln2": ((L, d), F32),
             "wg": ((L, d, f), bf), "wu": ((L, d, f), bf),
             "wd": ((L, f, d), bf)}
    return {"embed": ((vp, d), bf), "final_ln": ((d,), F32),
            "lm_head": ((d, vp), bf),
            "blocks": [layer]}


def leaf_std(model: dict):
    """How each weight is drawn: its standard deviation by leaf name."""
    out_std = 0.02 / math.sqrt(2 * model["num_layers"])

    def std(name: str) -> float:
        if name in ("embed", "lm_head", "wq", "wk", "wv", "wg", "wu"):
            return 0.02
        if name in ("wo", "wd"):
            return out_std
        if name in ("ln", "ln2", "final_ln"):
            return 0.1            # norm gains, used as (1 + gain)
        raise KeyError(f"no rule for drawing weight leaf {name!r}")
    return std


def abstract(model: dict, sharding=None):
    """``ShapeDtypeStruct`` tree; ``sharding(shape)`` places each leaf."""
    return jax.tree_util.tree_map(
        lambda sd: jax.ShapeDtypeStruct(
            sd[0], sd[1], sharding=sharding(sd[0]) if sharding else None),
        shapes(model), is_leaf=lambda x: isinstance(x, tuple)
        and len(x) == 2 and isinstance(x[0], tuple))


def make_weights(model: dict, seed: int, sharding=None):
    """The weights the program was given, made again from the seed."""
    abs_tree = abstract(model, sharding)
    if sharding is None:
        dev = jax.devices()[0]
        abs_tree = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(
                a.shape, a.dtype,
                sharding=jax.sharding.SingleDeviceSharding(dev)), abs_tree)
    return W.make(abs_tree, seed, leaf_std(model))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _q8(x):
    """Round to an 8-bit float, 4 exponent and 3 mantissa bits (largest
    finite 240), with one scale for the tensor; the gradient passes
    straight through.  ``reduce_precision`` and not a round trip through
    ``float8``/``bfloat16`` dtypes, which the TPU compiler may drop as
    excess precision."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 240.0
    q = lax.reduce_precision(x / s, exponent_bits=4, mantissa_bits=3) * s
    return x + lax.stop_gradient(q - x)


def mm(eq: str, a, b, prec: str):
    a, b = a.astype(F32), b.astype(F32)
    if prec == "fp8":
        a, b = _q8(a), _q8(b)
    elif prec != "f32":
        raise ValueError(f"unknown precision {prec!r}")
    return jnp.einsum(eq, a, b, precision=HIGHEST)


def rms_norm(x, gain, eps):
    x = x.astype(F32)
    y = x * lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * (1.0 + gain.astype(F32))


def rope(x, theta: float):
    """x [b, s, h, hd] at positions 0..s-1."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    freqs = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(model: dict, p: dict, x, prec: str):
    b, s, _ = x.shape
    H, KV, hd = model["num_heads"], model["num_kv_heads"], model["head_dim"]
    eps = model["norm_eps"]
    h = rms_norm(x, p["ln"], eps)
    q = mm("bsd,dk->bsk", h, p["wq"], prec).reshape(b, s, H, hd)
    k = mm("bsd,dk->bsk", h, p["wk"], prec).reshape(b, s, KV, hd)
    v = mm("bsd,dk->bsk", h, p["wv"], prec).reshape(b, s, KV, hd)
    q, k = rope(q, model["rope_theta"]), rope(k, model["rope_theta"])
    g = H // KV
    q = q.reshape(b, s, KV, g, hd) * hd ** -0.5
    sc = mm("bqkgh,bckh->bkgqc", q, k, prec)
    causal = jnp.tril(jnp.ones((s, s), bool))
    sc = jnp.where(causal, sc, -jnp.inf)
    pr = jax.nn.softmax(sc, axis=-1)
    o = mm("bkgqc,bckh->bqkgh", pr, v, prec).reshape(b, s, H * hd)
    x = x + mm("bsk,kd->bsd", o, p["wo"], prec)
    h = rms_norm(x, p["ln2"], eps)
    a = jax.nn.silu(mm("bsd,df->bsf", h, p["wg"], prec)) \
        * mm("bsd,df->bsf", h, p["wu"], prec)
    return x + mm("bsf,fd->bsd", a, p["wd"], prec)


def stored(x, dtype):
    """The value a weight of ``dtype`` holds, as f32; the gradient passes
    straight through to the f32 master.  Rounded by ``reduce_precision``:
    a round trip through ``bfloat16`` is one the TPU compiler may drop as
    excess precision, and then the reference trains unrounded weights."""
    if dtype == jnp.bfloat16:
        r = lax.reduce_precision(x, exponent_bits=8, mantissa_bits=7)
        return x + lax.stop_gradient(r - x)
    return x


def _as_stored(tree, dtypes):
    return jax.tree_util.tree_map(stored, tree, dtypes)


def hidden(model: dict, w: dict, tokens, prec: str, dtypes=None):
    """Final normed hidden states [b, s, d] in f32.  With ``dtypes`` the
    weights are f32 masters, rounded to their stored type layer by layer."""
    top = {k: w[k] for k in ("embed", "final_ln")}
    if dtypes is not None:
        top = _as_stored(top, {k: dtypes[k] for k in top})
    x = jnp.take(top["embed"], tokens, axis=0).astype(F32)

    def body(x, p):
        if dtypes is not None:
            p = _as_stored(p, dtypes["blocks"][0])
        return layer(model, p, x, prec), None

    x, _ = lax.scan(jax.checkpoint(body), x, w["blocks"][0])
    return rms_norm(x, top["final_ln"], model["norm_eps"])


def logits(model: dict, w: dict, h, prec: str, dtypes=None):
    head = w["lm_head"]
    if dtypes is not None:
        head = stored(head, dtypes["lm_head"])
    return mm("...d,dv->...v", h, head, prec)


def loss_sum(model: dict, w: dict, tokens, labels, prec: str, dtypes=None):
    lg = logits(model, w, hidden(model, w, tokens, prec, dtypes), prec,
                dtypes)
    lse = jax.nn.logsumexp(lg, axis=-1)
    lab = jnp.take_along_axis(lg, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - lab)


def dtype_tree(model: dict):
    return jax.tree_util.tree_map(
        lambda sd: sd[1], shapes(model),
        is_leaf=lambda x: isinstance(x, tuple) and len(x) == 2
        and isinstance(x[0], tuple))


# --------------------------------------------------------------------------
# training
# --------------------------------------------------------------------------
def lr_at(opt: dict, step):
    step = step.astype(F32)
    warm = jnp.minimum(step / max(opt["warmup_steps"], 1), 1.0)
    prog = jnp.clip((step - opt["warmup_steps"])
                    / max(opt["total_steps"] - opt["warmup_steps"], 1),
                    0.0, 1.0)
    return opt["learning_rate"] * warm * (0.55 + 0.45 * jnp.cos(jnp.pi * prog))


def train_step(model: dict, opt: dict, prec: str, rows: int):
    """``step(master, m, v, t, tokens, labels) -> (master, m, v, loss,
    clipped-gradient leaf norms)``; the loss and gradient are summed over
    blocks of ``rows`` sequences so the reference fits beside its state."""
    b1, b2 = opt["beta1"], opt["beta2"]
    dtypes = dtype_tree(model)

    def step(master, m, v, t, tokens, labels):
        n = tokens.shape[0] // rows
        tok = tokens.reshape(n, rows, -1)
        lab = labels.reshape(n, rows, -1)
        zero = jax.tree_util.tree_map(jnp.zeros_like, master)

        def blk(carry, inp):
            ls, gs = carry
            l, g = jax.value_and_grad(lambda w: loss_sum(
                model, w, inp[0], inp[1], prec, dtypes))(master)
            return (ls + l, jax.tree_util.tree_map(jnp.add, gs, g)), None

        (ls, gs), _ = lax.scan(blk, (jnp.zeros((), F32), zero), (tok, lab))
        count = tokens.size
        g = jax.tree_util.tree_map(lambda x: x / count, gs)
        gnorm = jnp.sqrt(sum(jnp.sum(x * x)
                             for x in jax.tree_util.tree_leaves(g)))
        clip = jnp.minimum(1.0, opt["grad_clip"] / (gnorm + 1e-6))
        g = jax.tree_util.tree_map(lambda x: x * clip, g)
        t = t + 1
        lr = lr_at(opt, t)
        bc1, bc2 = 1 - b1 ** t.astype(F32), 1 - b2 ** t.astype(F32)
        m = jax.tree_util.tree_map(lambda a, x: b1 * a + (1 - b1) * x, m, g)
        v = jax.tree_util.tree_map(lambda a, x: b2 * a + (1 - b2) * x * x,
                                   v, g)

        def upd(w, mi, vi):
            decay = opt["weight_decay"] * w if w.ndim > 1 else 0.0
            return w - lr * ((mi / bc1) / (jnp.sqrt(vi / bc2) + opt["eps"])
                             + decay)

        master = jax.tree_util.tree_map(upd, master, m, v)
        return master, m, v, ls / count, W.leaf_norms(g)

    return step


def run_training(model: dict, opt: dict, seed: int, batches, *, prec: str,
                 rows: int, sharding=None) -> dict:
    """Three (or however many batches) steps from the seed's weights:
    each step's loss, the first step's clipped-gradient leaf norms, and the
    leaf norms of the master weights' change after the last step."""
    w0 = make_weights(model, seed, sharding)
    master = jax.tree_util.tree_map(lambda x: x.astype(F32), w0)
    del w0
    m = jax.tree_util.tree_map(jnp.zeros_like, master)
    v = jax.tree_util.tree_map(jnp.zeros_like, master)
    step = jax.jit(train_step(model, opt, prec, rows),
                   donate_argnums=(0, 1, 2))
    t = jnp.zeros((), jnp.int32)
    losses, first_g = [], None
    for i, (tokens, labels) in enumerate(batches):
        master, m, v, loss, gn = step(master, m, v, t + i,
                                      jnp.asarray(tokens),
                                      jnp.asarray(labels))
        losses.append(float(loss))
        if first_g is None:
            first_g = {k: np.asarray(x) for k, x in gn.items()}
    del m, v
    w0 = make_weights(model, seed, sharding)
    change = jax.jit(lambda a, b: W.leaf_norms(jax.tree_util.tree_map(
        lambda x, y: x - y.astype(F32), a, b)))(master, w0)
    return {"losses": losses, "grad": first_g,
            "change": {k: np.asarray(x) for k, x in change.items()}}


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------
def served_gaps(model: dict, w: dict, tokens, rows, cols, served, *,
                control: bool, block: int = 4, chunk: int = 256):
    """How far each served token's logit lies below the reference's best.

    ``tokens`` [n, T]: each request's prompt and served tokens, padded at
    the end; the token at ``(rows[i], cols[i] + 1)`` was served as
    ``served[i]``.  Returns the gaps of the served tokens and, with
    ``control``, the gaps of the tokens the fp8 control puts first at the
    same positions (both measured in the f32 reference's logits)."""
    n, T = tokens.shape

    def hid(prec):
        h = lax.map(lambda t: hidden(model, w, t, prec),
                    tokens.reshape(n // block, block, T))
        return h.reshape(n, T, -1)[rows, cols]

    h32 = hid("f32")
    h8 = hid("fp8") if control else h32
    k = rows.shape[0]

    def gaps(inp):
        a, b, s = inp
        lg = logits(model, w, a, "f32")
        best = jnp.max(lg, axis=-1)
        g = best - jnp.take_along_axis(lg, s[:, None], axis=-1)[:, 0]
        if not control:
            return g, g
        c = jnp.argmax(logits(model, w, b, "fp8"), axis=-1)
        return g, best - jnp.take_along_axis(lg, c[:, None], axis=-1)[:, 0]

    split = (lambda x: x.reshape((k // chunk, chunk) + x.shape[1:]))
    g, gc = lax.map(gaps, (split(h32), split(h8), split(served)))
    return g.reshape(k), gc.reshape(k)
