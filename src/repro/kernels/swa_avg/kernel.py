"""Fused streaming weight-average Pallas kernel.

Phase 3 of SWAP (and every SWA sample step) folds a full model's weights into
a running mean. On TPU this is a pure HBM-bandwidth op; the kernel streams
(8, 1024)-float32 VMEM tiles (8 sublanes x 8·128 lanes) and fuses the scale +
add so each buffer is read once and written once — no intermediate
(w - avg) materialization in HBM, which is what the naive jnp expression
would allocate.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch

_LANES = 1024   # 8 * 128, one VREG row of lanes
_SUBS = 8


def _avg_kernel(n_ref, avg_ref, w_ref, o_ref):
    n = n_ref[0, 0]
    avg = avg_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    # divide, NOT multiply-by-reciprocal: elementwise ops then match the
    # jnp reference exactly, so kernel and reference stay BITWISE equal
    # (the op is HBM-bandwidth-bound; the VPU divide is free here)
    o_ref[...] = (avg + (w - avg) / (n + 1.0)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def running_average_pallas(avg, w, n, *, interpret: bool | None = None):
    """avg, w: 1-D same-length arrays; n: scalar float count.
    ``interpret=None`` resolves per backend (repro.kernels.dispatch)."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    assert avg.ndim == 1 and avg.shape == w.shape
    size = avg.shape[0]
    tile = _SUBS * _LANES
    pad = (-size) % tile
    ap = jnp.pad(avg, (0, pad)).reshape(-1, _SUBS, _LANES)
    wp = jnp.pad(w, (0, pad)).reshape(-1, _SUBS, _LANES)
    nf = jnp.asarray(n, jnp.float32).reshape(1, 1)

    out = pl.pallas_call(
        _avg_kernel,
        out_shape=jax.ShapeDtypeStruct(ap.shape, avg.dtype),
        grid=(ap.shape[0],),
        in_specs=[
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
            pl.BlockSpec((1, _SUBS, _LANES), lambda i: (i, 0, 0)),
            pl.BlockSpec((1, _SUBS, _LANES), lambda i: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, _SUBS, _LANES), lambda i: (i, 0, 0)),
        interpret=interpret,
        name="swa_avg_pallas",
    )(nf, ap, wp)
    return out.reshape(-1)[:size]
