"""Weights made from the seed, on the device, in one jitted call.

The program's parameter tree gives the layout (leaf paths, shapes, dtypes,
shardings); the values are the benchmark's own: each leaf is drawn from a
key that depends only on the seed and the leaf's path, so the reference can
make the very same leaf again without anything the program made.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

def base_key(seed: int):
    seed = int(seed)
    k = jax.random.fold_in(jax.random.key(0), np.uint32(seed & 0xFFFFFFFF))
    return jax.random.fold_in(k, np.uint32(seed >> 32))


def path_str(path) -> str:
    return jax.tree_util.keystr(path)


def leaf_name(path) -> str:
    last = path[-1]
    return str(getattr(last, "key", getattr(last, "idx", last)))


def draw(key, pstr: str, shape, dtype, std: float):
    k = jax.random.fold_in(key, np.uint32(zlib.crc32(pstr.encode())))
    return (jax.random.normal(k, shape, jnp.float32) * std).astype(dtype)


def make(abstract, seed: int, leaf_std):
    """Arrays shaped, typed and sharded like ``abstract`` (a tree of
    ``ShapeDtypeStruct`` with shardings); ``leaf_std(name)`` is each leaf's
    standard deviation, by the rule of the configuration's reference."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    meta = [(path_str(p), tuple(a.shape), a.dtype, leaf_std(leaf_name(p)))
            for p, a in leaves]
    shardings = jax.tree_util.tree_unflatten(
        treedef, [a.sharding for _, a in leaves])

    def build(key):
        return jax.tree_util.tree_unflatten(treedef, [
            draw(key, ps, shp, dt, std) for ps, shp, dt, std in meta])

    return jax.jit(build, out_shardings=shardings)(base_key(seed))


def leaf_norms(tree):
    """The norm of every leaf, per layer for the stacked block leaves
    (``['blocks']...``, layers first)."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        ps = path_str(path)
        x = x.astype(jnp.float32)
        if ps.startswith("['blocks']"):
            out[ps] = jnp.sqrt(jnp.sum(x * x, axis=tuple(range(1, x.ndim))))
        else:
            out[ps] = jnp.sqrt(jnp.sum(x * x))[None]
    return out
