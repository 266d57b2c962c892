"""Weights drawn from a run's seed, one leaf at a time, on the device.

Every leaf of a parameter tree is a function of the seed, the leaf's path
(``layers/attn/wqkv``) and, for the stacked per-layer leaves, the layer
index. So the harness can make the whole tree in one jitted call, and the
plain reference can make one layer again after the program's state is
gone, and both get the same numbers. This module imports nothing of the
program: the tree's paths and shapes are handed in.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

ONES = frozenset({"ln1", "ln2", "ln_cross", "final_norm", "q_norm", "k_norm", "D"})
OUT_PROJECTIONS = frozenset({"wo", "out_proj"})


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number below 2**64 (negatives wrap)."""
    s = seed % (1 << 64)
    return jax.random.fold_in(jax.random.PRNGKey(s & 0xFFFFFFFF), s >> 32)


def _path_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def leaf(key, path: str, shape: tuple, num_layers: int, layer=None) -> jax.Array:
    """Float32 value of one leaf (one layer's slice where ``layer`` is given;
    ``layer`` may be traced)."""
    k = _path_key(key, path)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    name = path.rsplit("/", 1)[-1]
    if name in ONES:
        return jnp.ones(shape, jnp.float32)
    if name == "conv_b":
        return jnp.zeros(shape, jnp.float32)
    if name == "A_log":
        return jnp.log(jax.random.uniform(k, shape, jnp.float32, 1.0, 16.0))
    if name == "dt_bias":
        dt = jnp.exp(jax.random.uniform(k, shape, jnp.float32,
                                        math.log(1e-3), math.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    if name == "conv_w":
        return jax.random.uniform(k, shape, jnp.float32, -0.5, 0.5)
    fan_in = shape[-1] if name == "embed" else shape[-2]
    std = fan_in ** -0.5
    if name in OUT_PROJECTIONS:
        std /= math.sqrt(2.0 * num_layers)
    return jax.random.normal(k, shape, jnp.float32) * std


def _stacked(key, path, shape, num_layers, dtype):
    """(L, ...) leaf made one layer at a time, cast as it is made, so the
    float32 temporary is one layer's."""
    return jax.lax.map(
        lambda i: leaf(key, path, shape[1:], num_layers, i).astype(dtype),
        jnp.arange(shape[0]))


def paths(tree) -> list[str]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return ["/".join(str(p.key) for p in kp) for kp, _ in flat]


def make(struct, seed: int, num_layers: int, out_shardings=None):
    """The tree ``struct`` describes (``jax.ShapeDtypeStruct`` leaves), made
    on the device in one jitted call."""
    names = paths(struct)
    leaves, treedef = jax.tree_util.tree_flatten(struct)

    def build(key):
        out = []
        for name, s in zip(names, leaves):
            if name.startswith("layers/"):
                out.append(_stacked(key, name, s.shape, num_layers, s.dtype))
            else:
                out.append(leaf(key, name, s.shape, num_layers).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build, out_shardings=out_shardings)(seed_key(seed))


def slice_norms(tree) -> dict:
    """Float32 norm of every leaf, per layer for the stacked leaves:
    ``{(path, layer or None): norm}`` (one jitted call, read to the host)."""
    names = paths(tree)
    leaves = jax.tree_util.tree_leaves(tree)

    def norms(xs):
        out = []
        for name, x in zip(names, xs):
            x = x.astype(jnp.float32)
            if name.startswith("layers/"):
                out.append(jnp.sqrt(jnp.sum(x.reshape(x.shape[0], -1) ** 2, axis=1)))
            else:
                out.append(jnp.sqrt(jnp.sum(x ** 2))[None])
        return out

    vals = jax.jit(norms)(leaves)
    return _keyed(names, vals)


def change_norms(tree, seed: int, num_layers: int) -> dict:
    """Norm of (leaf - its seed value) per leaf and layer, the seed value
    made again one layer at a time: ``{(path, layer or None): norm}``."""
    names = paths(tree)
    leaves = jax.tree_util.tree_leaves(tree)

    def norms(key, xs):
        out = []
        for name, x in zip(names, xs):
            if name.startswith("layers/"):
                out.append(jax.lax.map(
                    lambda il: jnp.sqrt(jnp.sum((il[1].astype(jnp.float32) - leaf(
                        key, name, x.shape[1:], num_layers, il[0])) ** 2)),
                    (jnp.arange(x.shape[0]), x)))
            else:
                out.append(jnp.sqrt(jnp.sum(
                    (x.astype(jnp.float32) - leaf(key, name, x.shape, num_layers)) ** 2))[None])
        return out

    vals = jax.jit(norms)(seed_key(seed), leaves)
    return _keyed(names, vals)


def _keyed(names, vals) -> dict:
    out = {}
    for name, v in zip(names, jax.device_get(vals)):
        if name.startswith("layers/"):
            for i, x in enumerate(v):
                out[(name, i)] = float(x)
        else:
            out[(name, None)] = float(v[0])
    return out
