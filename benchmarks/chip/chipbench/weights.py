"""Seeded weights, made by the benchmark and not by the program.

The program decides only the layout: which leaves exist and their shapes
(``jax.eval_shape`` of its init, which computes no values). Every value is
drawn here from ``--seed`` and the leaf's path, by a rule of its role, so the
program under test and the plain reference read the same numbers and
neither made them. All leaves are made on the device in one jitted call.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp


def path_str(path) -> str:
    parts = []
    for k in path:
        parts.append(str(getattr(k, "key", getattr(k, "idx", k))))
    return "/".join(parts)


def _leaf(key, path: str, shape, dtype):
    """One leaf from its role, named by the last part of its path."""
    name = path.rsplit("/", 1)[-1]
    if name in ("scale", "norm_scale", "D"):
        return jnp.ones(shape, dtype)
    if name == "table":                                   # embedding
        return (0.02 * jax.random.normal(key, shape)).astype(dtype)
    if name == "A_log":                                   # A in [1, 16]
        return jnp.log(jax.random.uniform(key, shape, minval=1.0,
                                          maxval=16.0)).astype(dtype)
    if name == "dt_bias":
        # softplus(dt_bias) log-uniform in [1e-3, 1e-1] (Mamba-2's rule)
        u = jax.random.uniform(key, shape)
        dt = jnp.exp(u * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    if len(shape) >= 2:                                   # matmul weights
        std = 1.0 / math.sqrt(shape[-2])
        return (std * jax.random.normal(key, shape)).astype(dtype)
    return jnp.zeros(shape, dtype)                        # biases


def make(shapes, seed: int, dtype) -> dict:
    """Weights for the tree of ``ShapeDtypeStruct`` ``shapes``, drawn from
    ``seed``; floating leaves in ``dtype``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(base):
        out = []
        for path, s in leaves:
            p = path_str(path)
            key = jax.random.fold_in(base, zlib.crc32(p.encode()) & 0x7FFFFFFF)
            out.append(_leaf(key, p, s.shape, dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))


def seed_key(seed: int):
    """A PRNG key for any whole-number seed (64 bits are folded in)."""
    seed = int(seed) % (1 << 64)
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)
