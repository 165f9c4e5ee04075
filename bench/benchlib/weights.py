"""Random weights from the seed, made on the device in one jitted call.

The benchmark makes the weights, hands them to the program, and hands the
same arrays to the plain reference, so neither depends on the other's
initialiser. Each leaf of the program's parameter tree is drawn by its
name, after the published initialisations (Mamba-2's A and dt ranges,
0.02 embeddings, fan-in scaled matrices); norm scales start at 0 because
the program's RMSNorm multiplies by (1 + scale).
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def _leaf(name: str, shape, dtype, key):
    f32 = jnp.float32
    if name == "scale":
        return jnp.zeros(shape, dtype)
    if name == "D":
        return jnp.ones(shape, dtype)
    if name == "A_log":  # A = -exp(A_log), A ~ U(1, 16)
        return jnp.log(jax.random.uniform(key, shape, f32, 1.0, 16.0)
                       ).astype(dtype)
    if name == "dt_bias":  # softplus(dt_bias) = dt, log-uniform [1e-3, 0.1]
        dt = jnp.exp(jax.random.uniform(key, shape, f32, math.log(1e-3),
                                        math.log(1e-1)))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if name == "table":
        return (0.02 * jax.random.normal(key, shape, f32)).astype(dtype)
    if len(shape) >= 2:  # matrices and depthwise conv taps: fan-in scaled
        std = shape[-2] ** -0.5
        return (std * jax.random.normal(key, shape, f32)).astype(dtype)
    raise ValueError(f"no initialisation rule for parameter {name!r} "
                     f"{tuple(shape)}")


def _path_str(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def generator(shapes):
    """Jitted ``key -> params`` for a tree of ``ShapeDtypeStruct``."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def make(key):
        leaves = []
        for path, s in flat:
            name = str(getattr(path[-1], "key", path[-1]))
            k = jax.random.fold_in(key, zlib.crc32(_path_str(path).encode())
                                   & 0x7FFFFFFF)
            leaves.append(_leaf(name, s.shape, s.dtype, k))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(make)


def seed_key(seed: int):
    """A PRNG key for any non-negative seed (more than 32 bits allowed)."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0x7FFFFFFF)
    rest = seed >> 31
    while rest:
        key = jax.random.fold_in(key, rest & 0x7FFFFFFF)
        rest >>= 31
    return key
