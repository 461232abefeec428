"""Arithmetic the plain references share: float32 matmuls at the highest
precision, and the float8 control.

``mode="fp8"`` is the benchmarks' control: every matmul, forward and
backward, takes float8 (e4m3) operands with one scale per tensor and
accumulates in float32.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def _fp8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


@jax.custom_vjp
def _mm8(a, b):
    return jnp.matmul(_fp8(a), _fp8(b))


def _mm8_fwd(a, b):
    return _mm8(a, b), (a, b)


def _mm8_bwd(res, g):
    a, b = res
    ga = jnp.matmul(_fp8(g), jnp.swapaxes(_fp8(b), -1, -2))
    gb = jnp.matmul(jnp.swapaxes(_fp8(a), -1, -2), _fp8(g))
    # batched operands: sum the broadcast batch axes back out of gb
    while gb.ndim > b.ndim:
        gb = gb.sum(0)
    return ga, gb


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(a, b, mode):
    if mode == "fp8":
        return _mm8(a, b)
    return jnp.matmul(a, b)


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w
