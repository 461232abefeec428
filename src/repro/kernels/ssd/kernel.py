"""Mamba-2 SSD intra-chunk kernel in Pallas.

TPU adaptation (vs the Triton SSD kernels in the Mamba-2 release):
  * The O(L^2) intra-chunk block — (C·Bᵀ ∘ decay-mask) @ (dt·x) — is the
    MXU hot spot. The grid is (sequence·group, chunk, head of the group),
    heads innermost: B and C are read in the model's own (B, S, G, N)
    layout, once per (sequence, group, chunk), since the pipeline fetches
    nothing for a block index that does not change, and C·Bᵀ is computed
    at the group's first head into VMEM and reused by the rest. What
    depends on a head's dt and A (the decay matrix, y, the chunk state and
    cum) stays per head. The backward sums ``dGseg`` and the state terms
    of dB over the group's heads in VMEM and multiplies by B and C once,
    at the group's last head. Chunk length L and head dim P are
    VMEM-resident tiles (L, P aligned to 128 by the caller for real-TPU
    runs; N too when G > 1).
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
from jax.experimental.pallas import tpu as pltpu

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


def _cbt(c_ref, b_ref):
    """The group's C·Bᵀ, (L, L) in f32."""
    return jax.lax.dot_general(c_ref[0].astype(jnp.float32),
                               b_ref[0].astype(jnp.float32),
                               (((1,), (1,)), ((), ())))


def _ssd_chunk_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref,
                      y_ref, state_ref, cum_ref, scores_ref):
    @pl.when(pl.program_id(2) == 0)             # the group's first head
    def _():
        scores_ref[...] = _cbt(c_ref, b_ref)

    x = x_ref[0].astype(jnp.float32)            # (L, P)
    dt_r = dt_ref[0].astype(jnp.float32)        # (1, L)
    bm = b_ref[0].astype(jnp.float32)           # (L, N)
    a = a_ref[0, 0, 0]                          # scalar A (negative)

    ch = _Chunk(x.shape[0])
    dt = ch.col(dt_r)                           # (L, 1)
    cum = ch.cumsum(dt_r * a)                   # (L, 1)
    cum_r = ch.row(cum)                         # (1, L)

    # segsum decay matrix: seg[i, j] = exp(cum_i - cum_j) for i >= j else 0
    seg = jnp.exp(jnp.where(ch.tri, cum - cum_r, -jnp.inf))

    dx = dt * x                                                     # (L, P)
    y = jax.lax.dot(scores_ref[...] * seg, dx)                      # (L, P)

    # chunk-local final state: sum_j exp(cum_end - cum_j) dt_j x_j ⊗ B_j
    w = jnp.exp(ch.at_last(cum) - cum) * dt                         # (L, 1)
    state = jax.lax.dot_general(x, bm * w,
                                (((0,), (0,)), ((), ())))           # (P, N)

    y_ref[0, ...] = y.astype(y_ref.dtype)
    state_ref[0, 0, ...] = state
    cum_ref[0, ...] = cum_r


def _ssd_chunk_bwd_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref,
                          dy_ref, dstate_ref, dcum_ref,
                          dx_ref, ddt_ref, db_ref, dc_ref, da_ref,
                          scores_ref, dgseg_ref):
    """Intra-chunk SSD backward. Given cotangents of (y_intra, chunk-local
    state, cum), produce (dx, ddt, da) for one (head, chunk) tile and add
    the head's share to its group's (dB, dC). All L×L work is MXU matmuls;
    cum is recomputed in VMEM (cheaper than streaming it back from HBM).
    The group's dB / dC blocks stay resident across its heads: dB gathers
    the state terms, ``dgseg_ref`` the heads' ``dG ∘ seg``, and the last
    head multiplies that sum by B and C once."""
    head = pl.program_id(2)

    @pl.when(head == 0)
    def _():
        scores_ref[...] = _cbt(c_ref, b_ref)
        dgseg_ref[...] = jnp.zeros(dgseg_ref.shape, jnp.float32)
        db_ref[...] = jnp.zeros(db_ref.shape, jnp.float32)

    x = x_ref[0].astype(jnp.float32)            # (L, P)
    dt_r = dt_ref[0].astype(jnp.float32)        # (1, L)
    bm = b_ref[0].astype(jnp.float32)           # (L, N)
    a = a_ref[0, 0, 0]
    dy = dy_ref[0].astype(jnp.float32)          # (L, P)
    dS = dstate_ref[0, 0].astype(jnp.float32)   # (P, N)
    dcum_r = dcum_ref[0].astype(jnp.float32)    # (1, L) from inter-chunk vjp

    ch = _Chunk(x.shape[0])
    dt = ch.col(dt_r)                           # (L, 1)
    cum = ch.cumsum(dt_r * a)                   # (L, 1)
    seg = jnp.exp(jnp.where(ch.tri, cum - ch.row(cum), -jnp.inf))
    G = scores_ref[...] * seg
    dx_in = dt * x                                                   # (L,P)

    # --- y_intra = G @ dx_in ---
    dG = jax.lax.dot_general(dy, dx_in, (((1,), (1,)), ((), ())))    # (L,L)
    d_dx = jax.lax.dot_general(G, dy, (((0,), (0,)), ((), ())))      # (L,P)
    dgseg_ref[...] += dG * seg                                       # masked
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
    db_ref[0, ...] += w * jax.lax.dot(x, dS)                         # (L,N)
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
    da_ref[0, 0] = jnp.sum(dt_r * rev, axis=1, keepdims=True)

    @pl.when(head == pl.num_programs(2) - 1)    # the group's last head
    def _():
        dgs = dgseg_ref[...]                    # Σ_heads dG ∘ seg
        dc_ref[0, ...] = jax.lax.dot(dgs, bm)                        # (L,N)
        db_ref[0, ...] += jax.lax.dot_general(
            dgs, c_ref[0].astype(jnp.float32), (((0,), (0,)), ((), ())))


class _Grid:
    """Grid (sequence·group, chunk, head of the group) and its index maps.
    Per-head arrays are flattened to (B·H, S, ·), where the group's heads
    are consecutive (head h reads group h // rep), so grid step
    (bg, ci, r) is head ``bg·rep + r``; B and C, and dB and dC, are
    (B, S, G·N), read per (sequence, group, chunk)."""

    def __init__(self, Bsz, H, G, nc, chunk):
        self.rep, self.G, self.chunk = H // G, G, chunk
        self.grid = (Bsz * G, nc, self.rep)

    def head(self, tail):
        """Block (1, chunk, tail) of a per-head (B·H, S, tail) array."""
        return pl.BlockSpec((1, self.chunk, tail),
                            lambda bg, ci, r: (bg * self.rep + r, ci, 0))

    def head_row(self):
        """Block (1, 1, chunk) of a per-head (B·H, 1, S) array."""
        return pl.BlockSpec((1, 1, self.chunk),
                            lambda bg, ci, r: (bg * self.rep + r, 0, ci))

    def head_state(self, shape):
        """Block (1, 1) + shape of a per-head (B·H, nc) + shape array."""
        return pl.BlockSpec(
            (1, 1) + shape,
            lambda bg, ci, r: (bg * self.rep + r, ci) + (0,) * len(shape))

    def head_a(self):
        return pl.BlockSpec((1, 1, 1),
                            lambda bg, ci, r: (bg * self.rep + r, 0, 0))

    def group(self, N):
        """Block (1, chunk, N) of a (B, S, G·N) array: the group's."""
        return pl.BlockSpec((1, self.chunk, N),
                            lambda bg, ci, r: (bg // self.G, ci,
                                               bg % self.G))

    def params(self):
        # the heads carry the group's scratch: they run in order
        return pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))


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
    nc = S // chunk
    BH = Bsz * H
    g = _Grid(Bsz, H, G, nc, chunk)

    xf = jnp.swapaxes(x, 1, 2).reshape(BH, S, P)
    dtf = jnp.swapaxes(dt, 1, 2).reshape(BH, 1, S)
    af = jnp.tile(A.astype(jnp.float32)[None, :], (Bsz, 1)).reshape(BH, 1, 1)
    dyf = jnp.swapaxes(dy.astype(jnp.float32), 1, 2).reshape(BH, S, P)
    dsf = jnp.swapaxes(dstates.astype(jnp.float32), 1, 2).reshape(
        BH, nc, P, N)
    dcf = jnp.swapaxes(dcum.astype(jnp.float32), 1, 2).reshape(BH, 1, S)

    dx, ddt, db, dc, da = pl.pallas_call(
        _ssd_chunk_bwd_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, S, G * N), jnp.float32),
            jax.ShapeDtypeStruct((Bsz, S, G * N), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, 1, 1), jnp.float32),
        ),
        grid=g.grid,
        in_specs=[g.head(P), g.head_row(), g.group(N), g.group(N), g.head_a(),
                  g.head(P), g.head_state((P, N)), g.head_row()],
        out_specs=(g.head(P), g.head_row(), g.group(N), g.group(N),
                   g.head_state((1, 1))),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32),   # C·Bᵀ
                        pltpu.VMEM((chunk, chunk), jnp.float32)],  # Σ dGseg
        compiler_params=g.params(),
        interpret=interpret,
        name="ssd_chunk_pallas_bwd",
    )(xf, dtf, Bm.reshape(Bsz, S, G * N), Cm.reshape(Bsz, S, G * N), af,
      dyf, dsf, dcf)

    def unflat(t, extra):
        return jnp.swapaxes(t.reshape((Bsz, H) + extra), 1, 2)

    dx_out = unflat(dx, (S, P))
    ddt_out = unflat(ddt, (S,))
    dA_out = jnp.sum(da.reshape(Bsz, H, nc), axis=(0, 2))
    return (dx_out, ddt_out, dA_out, db.reshape(Bsz, S, G, N),
            dc.reshape(Bsz, S, G, N))


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
    assert S % chunk == 0, (S, chunk)
    nc = S // chunk
    BH = Bsz * H
    g = _Grid(Bsz, H, G, nc, chunk)

    # flatten to (B*H, S, ·) batch-head major
    xf = jnp.swapaxes(x, 1, 2).reshape(BH, S, P)
    dtf = jnp.swapaxes(dt, 1, 2).reshape(BH, 1, S)
    af = jnp.tile(A.astype(jnp.float32)[None, :], (Bsz, 1)).reshape(BH, 1, 1)

    y, states, cum = pl.pallas_call(
        _ssd_chunk_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((BH, S, P), jnp.float32),
            jax.ShapeDtypeStruct((BH, nc, P, N), jnp.float32),
            jax.ShapeDtypeStruct((BH, 1, S), jnp.float32),
        ),
        grid=g.grid,
        in_specs=[g.head(P), g.head_row(), g.group(N), g.group(N),
                  g.head_a()],
        out_specs=(g.head(P), g.head_state((P, N)), g.head_row()),
        scratch_shapes=[pltpu.VMEM((chunk, chunk), jnp.float32)],  # C·Bᵀ
        compiler_params=g.params(),
        interpret=interpret,
        name="ssd_chunk_pallas",
    )(xf, dtf, Bm.reshape(Bsz, S, G * N), Cm.reshape(Bsz, S, G * N), af)

    y = jnp.swapaxes(y.reshape(Bsz, H, S, P), 1, 2)
    states = jnp.swapaxes(states.reshape(Bsz, H, nc, P, N), 1, 2)
    cum = jnp.swapaxes(cum.reshape(Bsz, H, S), 1, 2)
    return y, states, cum
