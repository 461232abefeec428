"""Mamba-2 SSD intra-chunk kernel in Pallas.

TPU adaptation (vs the Triton SSD kernels in the Mamba-2 release):
  * The O(L^2) intra-chunk block — (C·Bᵀ ∘ decay-mask) @ (dt·x) — is the
    MXU hot spot; it runs as one Pallas program per (batch·head, chunk) with
    chunk length L and head dim P as VMEM-resident tiles (L, P aligned to
    128 by the caller for real-TPU runs).
  * The inter-chunk state recurrence is sequential and tiny
    (nc elements of (P,N) state); it stays in JAX as lax.associative_scan —
    on TPU this is a log-depth tree of elementwise ops, not worth a kernel.
  * No shared-memory banking / warp semantics to port: the decay (segsum)
    matrix is built with broadcasted iota + masked reductions inside VMEM.

The kernel emits, per chunk: the intra-chunk output, the chunk-local final
state contribution, and the in-chunk cumulative decay (needed by the
inter-chunk correction applied by the caller).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import dispatch


class _Chunk:
    """Index masks for one (L,)-long chunk. Per-position vectors live as
    (L, 1) columns or (1, L) rows; Mosaic has no cumsum, reverse or dynamic
    slice, so prefix sums, row<->column moves and the last element are
    masked (L, L) reductions (exact: each output sums one nonzero, or a
    prefix, in f32)."""

    def __init__(self, L):
        ii = jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
        jj = jax.lax.broadcasted_iota(jnp.int32, (L, L), 1)
        self.tri = ii >= jj
        self.eye = ii == jj
        self.last = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0) == L - 1

    def col(self, row):
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, col):
        return jnp.sum(jnp.where(self.eye, col, 0.0), axis=0, keepdims=True)

    def cumsum(self, row):
        """Inclusive prefix sum of a (1, L) row, as an (L, 1) column."""
        return jnp.sum(jnp.where(self.tri, row, 0.0), axis=1, keepdims=True)

    def rev_cumsum(self, col):
        """Suffix sum ``out_j = sum_{i >= j} col_i`` of a column, as a row."""
        return jnp.sum(jnp.where(self.tri, col, 0.0), axis=0, keepdims=True)

    def at_last(self, col):
        """``col[-1]`` as a (1, 1) array."""
        return jnp.sum(jnp.where(self.last, col, 0.0), axis=0, keepdims=True)


def _ssd_chunk_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref,
                      y_ref, state_ref, cum_ref):
    x = x_ref[0].astype(jnp.float32)            # (L, P)
    dt_r = dt_ref[0].astype(jnp.float32)        # (1, L)
    bm = b_ref[0].astype(jnp.float32)           # (L, N)
    cm = c_ref[0].astype(jnp.float32)           # (L, N)
    a = a_ref[0, 0, 0]                          # scalar A (negative)

    ch = _Chunk(x.shape[0])
    dt = ch.col(dt_r)                           # (L, 1)
    cum = ch.cumsum(dt_r * a)                   # (L, 1)
    cum_r = ch.row(cum)                         # (1, L)

    # segsum decay matrix: seg[i, j] = exp(cum_i - cum_j) for i >= j else 0
    seg = jnp.exp(jnp.where(ch.tri, cum - cum_r, -jnp.inf))

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # (L, L)
    dx = dt * x                                                     # (L, P)
    y = jax.lax.dot(scores * seg, dx)                               # (L, P)

    # chunk-local final state: sum_j exp(cum_end - cum_j) dt_j x_j ⊗ B_j
    w = jnp.exp(ch.at_last(cum) - cum) * dt                         # (L, 1)
    state = jax.lax.dot_general(x, bm * w,
                                (((0,), (0,)), ((), ())))           # (P, N)

    y_ref[0, ...] = y.astype(y_ref.dtype)
    state_ref[0, 0, ...] = state
    cum_ref[0, ...] = cum_r


def _ssd_chunk_bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref,
                          dy_ref, dstate_ref, dcum_ref,
                          dx_ref, ddt_ref, db_ref, dc_ref, da_ref):
    """Intra-chunk SSD backward. Given cotangents of (y_intra, chunk-local
    state, cum), produce (dx, ddt, dB, dC, da) for one (batch·head, chunk)
    tile. All L×L work is MXU matmuls; cum is recomputed in VMEM (cheaper
    than streaming it back from HBM)."""
    x = x_ref[0].astype(jnp.float32)            # (L, P)
    dt_r = dt_ref[0].astype(jnp.float32)        # (1, L)
    bm = b_ref[0].astype(jnp.float32)           # (L, N)
    cm = c_ref[0].astype(jnp.float32)           # (L, N)
    a = a_ref[0, 0, 0]
    dy = dy_ref[0].astype(jnp.float32)          # (L, P)
    dS = dstate_ref[0, 0].astype(jnp.float32)   # (P, N)
    dcum_r = dcum_ref[0].astype(jnp.float32)    # (1, L) from inter-chunk vjp

    ch = _Chunk(x.shape[0])
    dt = ch.col(dt_r)                           # (L, 1)
    cum = ch.cumsum(dt_r * a)                   # (L, 1)
    seg = jnp.exp(jnp.where(ch.tri, cum - ch.row(cum), -jnp.inf))
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())))  # C·Bᵀ
    G = scores * seg
    dx_in = dt * x                                                   # (L,P)

    # --- y_intra = G @ dx_in ---
    dG = jax.lax.dot_general(dy, dx_in, (((1,), (1,)), ((), ())))    # (L,L)
    d_dx = jax.lax.dot_general(G, dy, (((0,), (0,)), ((), ())))      # (L,P)
    dGseg = dG * seg                                                 # masked
    dc = jax.lax.dot(dGseg, bm)                                      # (L,N)
    db = jax.lax.dot_general(dGseg, cm, (((0,), (0,)), ((), ())))    # (L,N)
    E = dG * G                                                       # (L,L)
    dcum = (ch.col(dcum_r) + jnp.sum(E, axis=1, keepdims=True)
            - ch.col(jnp.sum(E, axis=0, keepdims=True)))             # (L,1)

    # --- state = Σ_j w_j x_j ⊗ B_j, w_j = exp(cum_L - cum_j)·dt_j ---
    wexp = jnp.exp(ch.at_last(cum) - cum)                            # (L,1)
    w = wexp * dt
    # dw_j = x_j · (dS @ B_j);  dx_j += w_j (dS @ B_j);  dB_j += w_j (dSᵀ x_j)
    dS_b = jax.lax.dot_general(bm, dS, (((1,), (1,)), ((), ())))     # (L,P)
    dw = jnp.sum(x * dS_b, axis=1, keepdims=True)                    # (L,1)
    dx = w * dS_b
    db = db + w * jax.lax.dot(x, dS)                                 # (L,N)
    dcum = dcum - dw * w + jnp.where(
        ch.last, jnp.sum(dw * w, axis=0, keepdims=True), 0.0)
    ddt = dw * wexp

    # --- dx_in = dt ∘ x ---
    ddt = ddt + jnp.sum(d_dx * x, axis=1, keepdims=True)
    dx = dx + dt * d_dx

    # --- cum = cumsum(dt·a): reverse-cumsum the dcum ---
    rev = ch.rev_cumsum(dcum)                                        # (1,L)
    ddt_r = ch.row(ddt) + a * rev

    dx_ref[0, ...] = dx
    ddt_ref[0, ...] = ddt_r
    db_ref[0, ...] = db
    dc_ref[0, ...] = dc
    da_ref[0, 0] = jnp.sum(dt_r * rev, axis=1, keepdims=True)


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_pallas_bwd(x, dt, A, Bm, Cm, dy, dstates, dcum, *,
                         chunk: int = 128, interpret: bool | None = None):
    """Backward of ssd_chunk_pallas. Cotangents: dy (B,S,H,P) for y_intra,
    dstates (B,nc,H,P,N) for chunk-local states, dcum (B,S,H) for cum.
    Returns (dx, ddt, dA, dBm, dCm) with grouped B/C gradients summed over
    the heads sharing each group."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    nc = S // chunk
    BH = Bsz * H

    xf = jnp.swapaxes(x, 1, 2).reshape(BH, S, P)
    dtf = jnp.swapaxes(dt, 1, 2).reshape(BH, 1, S)
    bf = jnp.swapaxes(jnp.repeat(Bm, rep, axis=2), 1, 2).reshape(BH, S, N)
    cf = jnp.swapaxes(jnp.repeat(Cm, rep, axis=2), 1, 2).reshape(BH, S, N)
    af = jnp.tile(A.astype(jnp.float32)[None, :], (Bsz, 1)).reshape(BH, 1, 1)
    dyf = jnp.swapaxes(dy.astype(jnp.float32), 1, 2).reshape(BH, S, P)
    dsf = jnp.swapaxes(dstates.astype(jnp.float32), 1, 2).reshape(
        BH, nc, P, N)
    dcf = jnp.swapaxes(dcum.astype(jnp.float32), 1, 2).reshape(BH, 1, S)

    grid = (BH, nc)
    dx, ddt, db, dc, da = pl.pallas_call(
        _ssd_chunk_bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, S, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, 1, 1), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ci: (bh, 0, 0)),
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda bh, ci: (bh, ci, 0, 0)),
        ),
        interpret=interpret,
        name="ssd_chunk_pallas_bwd",
    )(xf, dtf, bf, cf, af, dyf, dsf, dcf)

    def unflat(t, extra):
        return jnp.swapaxes(t.reshape((Bsz, H) + extra), 1, 2)

    dx_out = unflat(dx, (S, P))
    ddt_out = unflat(ddt, (S,))
    dA_out = jnp.sum(da.reshape(Bsz, H, nc), axis=(0, 2))
    # grouped B/C: sum gradients over the rep heads sharing each group
    db_out = unflat(db, (S, N)).reshape(Bsz, S, G, rep, N).sum(3)
    dc_out = unflat(dc, (S, N)).reshape(Bsz, S, G, rep, N).sum(3)
    return dx_out, ddt_out, dA_out, db_out, dc_out


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_chunk_pallas(x, dt, A, Bm, Cm, *, chunk: int = 128,
                     interpret: bool | None = None):
    """Intra-chunk SSD. x: (B,S,H,P); dt: (B,S,H); A: (H,);
    Bm, Cm: (B,S,G,N) — returns (y_intra (B,S,H,P) f32,
    states (B,nc,H,P,N) f32, cum (B,S,H) f32). S % chunk must be 0.
    ``interpret=None`` resolves per backend (repro.kernels.dispatch)."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    Bsz, S, H, P = x.shape
    G, N = Bm.shape[2], Bm.shape[3]
    rep = H // G
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    BH = Bsz * H

    # flatten to (B*H, S, ·) batch-head major
    xf = jnp.swapaxes(x, 1, 2).reshape(BH, S, P)
    dtf = jnp.swapaxes(dt, 1, 2).reshape(BH, 1, S)
    bf = jnp.swapaxes(jnp.repeat(Bm, rep, axis=2), 1, 2).reshape(BH, S, N)
    cf = jnp.swapaxes(jnp.repeat(Cm, rep, axis=2), 1, 2).reshape(BH, S, N)
    af = jnp.tile(A.astype(jnp.float32)[None, :], (Bsz, 1)).reshape(BH, 1, 1)

    grid = (BH, nc)
    y, states, cum = pl.pallas_call(
        _ssd_chunk_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, chunk, N), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, 1), lambda bh, ci: (bh, 0, 0)),
        ],
        out_specs=(
            pl.BlockSpec((1, chunk, P), lambda bh, ci: (bh, ci, 0)),
            pl.BlockSpec((1, 1, P, N), lambda bh, ci: (bh, ci, 0, 0)),
            pl.BlockSpec((1, 1, chunk), lambda bh, ci: (bh, 0, ci)),
        ),
        interpret=interpret,
        name="ssd_chunk_pallas",
    )(xf, dtf, bf, cf, af)

    y = jnp.swapaxes(y.reshape(Bsz, H, S, P), 1, 2)
    states = jnp.swapaxes(states.reshape(Bsz, H, nc, P, N), 1, 2)
    cum = jnp.swapaxes(cum.reshape(Bsz, H, S), 1, 2)
    return y, states, cum
