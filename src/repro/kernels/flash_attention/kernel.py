"""Blockwise flash attention as a Pallas TPU kernel.

TPU adaptation notes (vs the CUDA flash-attention algorithm):
  * Tiles are BlockSpec-mapped HBM->VMEM blocks, (block_q x D) for Q/O and
    (block_k x D) for K/V, with D padded to a multiple of 128 by the caller
    so the MXU (128x128 systolic array) sees aligned matmul shapes.
  * The KV loop is the minor-most grid dimension; running max / sum / output
    accumulators live in VMEM scratch and persist across KV grid steps
    (TPU grid execution is sequential over the minor dimension, which is
    exactly the flash streaming pattern — no atomics / warp shuffles needed).
  * GQA is handled by the K/V index_map (query head h reads kv head h//G);
    no materialized head repetition in HBM.
  * Tile schedule (``_Tiles``): each (q tile, k tile) pair is dead (every
    position masked by causality or the sliding window), full (no
    position masked) or partial. A dead pair does no work and fetches
    nothing: its body sits under ``pl.when`` and the index map of the
    streamed operand repeats the nearest live block, so the pipeline skips
    the copy. A full pair skips the iota mask; a partial pair applies it
    with absolute-position comparison. Skipping a dead pair is exact: it
    would add p = 0 and rescale by 1.
  * Backward is flash-attention-2 style: the forward emits LSE; a dQ
    kernel accumulates over KV blocks, and a dK/dV kernel accumulates over
    (query-head-in-group x q-block) pairs via its minor grid dimension —
    GQA's head-group reduction becomes grid scheduling instead of atomics.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

NEG_INF = -1e30


def _min(a, b):
    return jnp.minimum(a, b) if isinstance(a, jax.Array) else np.minimum(a, b)


def _max(a, b):
    return jnp.maximum(a, b) if isinstance(a, jax.Array) else np.maximum(a, b)


def _div(a, b):
    """a // b for a >= 0: traced grid indices or numpy arrays."""
    return jax.lax.div(a, b) if isinstance(a, jax.Array) else a // b


@dataclasses.dataclass(frozen=True)
class _Tiles:
    """Static geometry of one call's (q tile, k tile) grid: which pairs
    hold unmasked positions, and the span of live tiles of a row or a
    column. Query row r sits at absolute position r + q_offset; rows at or
    past q_len and keys at or past kv_len are padding, masked in the tile
    that holds them. Methods take grid indices, traced (inside the kernel
    and its index maps) or numpy."""

    block_q: int
    block_k: int
    q_offset: int
    q_len: int
    kv_len: int
    causal: bool
    window: int

    @property
    def nq(self) -> int:
        return -(-self.q_len // self.block_q)

    @property
    def nk(self) -> int:
        return -(-self.kv_len // self.block_k)

    def _q_range(self, qi):
        """First and last absolute position of the tile's real rows."""
        end = _min((qi + 1) * self.block_q, self.q_len)
        return qi * self.block_q + self.q_offset, end - 1 + self.q_offset

    def _k_range(self, ki):
        end = _min((ki + 1) * self.block_k, self.kv_len)
        return ki * self.block_k, end - 1

    def pair(self, qi, ki):
        """(live, full): some position of the pair is unmasked / none is.
        Every tile holds a real row or key (the grid is the lengths
        rounded up to a tile), so only the mask can kill a pair."""
        q_lo, q_hi = self._q_range(qi)
        k_lo, k_hi = self._k_range(ki)
        live = True
        full = (((qi + 1) * self.block_q <= self.q_len)
                & ((ki + 1) * self.block_k <= self.kv_len))
        if self.causal:
            live &= k_lo <= q_hi
            full &= k_hi <= q_lo
        if self.window > 0:
            live &= k_hi > q_lo - self.window
            full &= k_lo > q_hi - self.window
        return live, full

    def k_span(self, qi):
        """(first, last) live k tile of q tile ``qi``, within [0, nk)."""
        q_lo, q_hi = self._q_range(qi)
        first, last = 0, self.nk - 1
        if self.window > 0:
            first = _min(_div(_max(q_lo - self.window + 1, 0), self.block_k),
                         last)
        if self.causal:
            last = _div(_min(q_hi, self.kv_len - 1), self.block_k)
        return first, last

    def q_span(self, ki):
        """(first, last) live q tile of k tile ``ki``, within [0, nq)."""
        k_lo, k_hi = self._k_range(ki)
        first, last = 0, self.nq - 1
        if self.causal:
            first = _min(_div(_max(k_lo - self.q_offset, 0), self.block_q),
                         last)
        if self.window > 0:
            row = _min(k_hi + self.window - 1 - self.q_offset, self.q_len - 1)
            last = _div(_max(row, 0), self.block_q)
        return first, last

    def mask(self, qi, ki):
        """The (block_q, block_k) mask of a partial pair."""
        iota = functools.partial(jax.lax.broadcasted_iota, jnp.int32,
                                 (self.block_q, self.block_k))
        row = qi * self.block_q + iota(0)
        kpos = ki * self.block_k + iota(1)
        qpos = row + self.q_offset
        m = (kpos < self.kv_len) & (row < self.q_len)
        if self.causal:
            m &= kpos <= qpos
        if self.window > 0:
            m &= kpos > qpos - self.window
        return m


def _clip(i, span):
    first, last = span
    return jnp.minimum(jnp.maximum(i, first), last)


def _by_class(tiles, qi, ki, body):
    """Run ``body(mask)`` on live pairs: with the pair's mask on partial
    ones, with ``None`` on full ones; dead pairs run nothing."""
    live, full = tiles.pair(qi, ki)

    @pl.when(full)
    def _():
        body(None)

    @pl.when(live & jnp.logical_not(full))
    def _():
        body(tiles.mask(qi, ki))


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
               scale: float, tiles: _Tiles):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(mask):
        q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
        k = k_ref[0].astype(jnp.float32)                  # (bk, D)
        v = v_ref[0].astype(jnp.float32)                  # (bk, D)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]                                # (bq, 1)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # fully-masked rows: m_new stays NEG_INF -> p would be exp(0)=1
        p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
        alpha = jnp.where(m_prev > NEG_INF / 2, alpha, 0.0)

        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
        m_ref[...] = m_new

    _by_class(tiles, qi, ki, step)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        l = l_ref[...]
        empty = l == 0.0                               # fully-masked query rows
        l = jnp.where(empty, 1.0, l)
        o_ref[0, ...] = (acc_ref[...] / l).astype(o_ref.dtype)
        # logsumexp for the backward pass; 0 for empty rows so that
        # exp(s - lse) underflows to 0 there (s stays at NEG_INF)
        lse_ref[0, 0] = jnp.where(empty[:, 0], 0.0,
                                  m_ref[:, 0] + jnp.log(l[:, 0]))


def _blocks(Sq, Skv, block_q, block_k):
    """Tile sizes as the kernel runs them: no larger than the sequence."""
    return min(block_q, max(Sq, 8)), min(block_k, max(Skv, 8))


def tile_schedule(Sq: int, Skv: int, *, causal: bool = True, window: int = 0,
                  q_offset: int = 0, block_q: int = 128,
                  block_k: int = 128) -> tuple:
    """(pairs, live pairs) of one head's (q tile, k tile) grid, as
    ``flash_attention_pallas_fwd`` runs it with these arguments."""
    bq, bk = _blocks(Sq, Skv, block_q, block_k)
    tiles = _Tiles(bq, bk, q_offset, Sq, Skv, causal, window)
    live, _ = tiles.pair(np.arange(tiles.nq)[:, None],
                         np.arange(tiles.nk)[None, :])
    live = np.broadcast_to(live, (tiles.nq, tiles.nk))
    return tiles.nq * tiles.nk, int(live.sum())


def _layout(q, k, v, block_q, block_k, interpret):
    """Flatten to (B*H, S, D) batch-head major, pad to block/lane multiples."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    Dp = max(128, (D + 127) // 128 * 128) if not interpret else D
    block_q, block_k = _blocks(Sq, Skv, block_q, block_k)
    Sqp = (Sq + block_q - 1) // block_q * block_q
    Skvp = (Skv + block_k - 1) // block_k * block_k

    def prep(x, S, Sp, NH):
        x = jnp.swapaxes(x, 1, 2).reshape(B * NH, S, x.shape[-1])
        return jnp.pad(x, ((0, 0), (0, Sp - S), (0, Dp - x.shape[-1])))

    return (prep(q, Sq, Sqp, H), prep(k, Skv, Skvp, KVH),
            prep(v, Skv, Skvp, KVH), Dp, block_q, block_k, Sqp, Skvp)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "q_offset", "block_q",
                     "block_k", "interpret"),
)
def flash_attention_pallas(q, k, v, *, causal: bool = True, window: int = 0,
                           scale: float | None = None, q_offset: int = 0,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool | None = None):
    """q: (B, Sq, H, D); k, v: (B, Skv, KVH, D). Returns (B, Sq, H, D)."""
    out, _ = flash_attention_pallas_fwd(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=block_q, block_k=block_k,
        interpret=interpret)
    return out


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "q_offset", "block_q",
                     "block_k", "interpret"),
)
def flash_attention_pallas_fwd(q, k, v, *, causal: bool = True,
                               window: int = 0, scale: float | None = None,
                               q_offset: int = 0, block_q: int = 128,
                               block_k: int = 128, interpret: bool | None = None):
    """Forward returning (out (B,Sq,H,D), lse (B,Sq,H) f32) for the
    backward kernels. ``interpret=None`` resolves per backend (compiled on
    TPU, interpreter elsewhere — repro.kernels.dispatch)."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = scale if scale is not None else D ** -0.5
    qf, kf, vf, Dp, block_q, block_k, Sqp, Skvp = _layout(
        q, k, v, block_q, block_k, interpret)
    tiles = _Tiles(block_q, block_k, q_offset, Sq, Skv, causal, window)
    grid = (B * H, tiles.nq, tiles.nk)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    def kv_map(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KVH + h // G, _clip(ki, tiles.k_span(qi)), 0)

    out, lse = pl.pallas_call(
        functools.partial(_fa_kernel, scale=scale, tiles=tiles),
        out_shape=(
            jax.ShapeDtypeStruct((B * H, Sqp, Dp), q.dtype),
            jax.ShapeDtypeStruct((B * H, 1, Sqp), jnp.float32),
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), q_map),
            pl.BlockSpec((1, block_k, Dp), kv_map),
            pl.BlockSpec((1, block_k, Dp), kv_map),
        ],
        out_specs=(
            pl.BlockSpec((1, block_q, Dp), q_map),
            pl.BlockSpec((1, 1, block_q), lse_map),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),     # running max
            pltpu.VMEM((block_q, 1), jnp.float32),     # running sum
            pltpu.VMEM((block_q, Dp), jnp.float32),    # output accumulator
        ],
        interpret=interpret,
        name="flash_attention_pallas_fwd",
    )(qf, kf, vf)

    out = jnp.swapaxes(out[:, :Sq, :D].reshape(B, H, Sq, D), 1, 2)
    lse = jnp.swapaxes(lse[:, 0, :Sq].reshape(B, H, Sq), 1, 2)
    return out, lse


# ---------------------------------------------------------------------------
# backward kernels (flash-attention-2 style: dQ pass + dK/dV pass)
# ---------------------------------------------------------------------------


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, scale, tiles: _Tiles):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(mask):
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]                              # (block_q,)
        delta = delta_ref[0, 0]                          # (block_q,)

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])
        acc_ref[...] += jax.lax.dot(ds, k) * scale

    _by_class(tiles, qi, ki, step)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[0, ...] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, scale,
                       tiles: _Tiles):
    ki, gq = pl.program_id(1), pl.program_id(2)
    qi = gq % tiles.nq

    @pl.when(gq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(mask):
        q = q_ref[0].astype(jnp.float32) * scale
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        do = do_ref[0].astype(jnp.float32)
        lse = lse_ref[0, 0]
        delta = delta_ref[0, 0]

        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
        if mask is not None:
            s = jnp.where(mask, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (bq, bk)
        dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
        dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
        ds = p * (dp - delta[:, None])                   # (bq, bk)
        dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

    _by_class(tiles, qi, ki, step)

    @pl.when(gq == pl.num_programs(2) - 1)
    def _():
        dk_ref[0, ...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[0, ...] = dv_acc[...].astype(dv_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "scale", "q_offset", "block_q",
                     "block_k", "interpret"),
)
def flash_attention_pallas_bwd(q, k, v, out, lse, do, *, causal: bool = True,
                               window: int = 0, scale: float | None = None,
                               q_offset: int = 0, block_q: int = 128,
                               block_k: int = 128, interpret: bool | None = None):
    """Flash backward. Returns (dq, dk, dv) with the input shapes.
    GQA: dK/dV accumulate over each kv head's G query heads via the grid."""
    if interpret is None:
        interpret = dispatch.interpret_default()
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = scale if scale is not None else D ** -0.5
    qf, kf, vf, Dp, block_q, block_k, Sqp, Skvp = _layout(
        q, k, v, block_q, block_k, interpret)
    dof = _layout(do, k, v, block_q, block_k, interpret)[0]
    # delta = rowsum(dO * O) — cheap elementwise, computed outside
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32), -1)
    deltaf = jnp.pad(jnp.swapaxes(delta, 1, 2).reshape(B * H, 1, Sq),
                     ((0, 0), (0, 0), (0, Sqp - Sq)))
    lsef = jnp.pad(jnp.swapaxes(lse, 1, 2).reshape(B * H, 1, Sq),
                   ((0, 0), (0, 0), (0, Sqp - Sq)))
    tiles = _Tiles(block_q, block_k, q_offset, Sq, Skv, causal, window)
    nq, nk = tiles.nq, tiles.nk

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def r_map(bh, qi, ki):
        return (bh, 0, qi)

    def kv_map(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KVH + h // G, _clip(ki, tiles.k_span(qi)), 0)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, scale=scale, tiles=tiles),
        out_shape=jax.ShapeDtypeStruct((B * H, Sqp, Dp), q.dtype),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), q_map),
            pl.BlockSpec((1, block_k, Dp), kv_map),
            pl.BlockSpec((1, block_k, Dp), kv_map),
            pl.BlockSpec((1, block_q, Dp), q_map),
            pl.BlockSpec((1, 1, block_q), r_map),
            pl.BlockSpec((1, 1, block_q), r_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, Dp), q_map),
        scratch_shapes=[pltpu.VMEM((block_q, Dp), jnp.float32)],
        interpret=interpret,
        name="flash_attention_pallas_bwd_dq",
    )(qf, kf, vf, dof, lsef, deltaf)

    # dK/dV: grid minor dim runs over (g, qi) pairs of this kv head; a dead
    # pair's q-side operands repeat the nearest live q tile of this head
    def head_and_q(bkv, ki, gq):
        b, hkv = bkv // KVH, bkv % KVH
        qi = _clip(gq % nq, tiles.q_span(ki))
        return b * H + hkv * G + gq // nq, qi

    def q_map2(bkv, ki, gq):
        bh, qi = head_and_q(bkv, ki, gq)
        return (bh, qi, 0)

    def r_map2(bkv, ki, gq):
        bh, qi = head_and_q(bkv, ki, gq)
        return (bh, 0, qi)

    def kv_map2(bkv, ki, gq):
        return (bkv, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, scale=scale, tiles=tiles),
        out_shape=(
            jax.ShapeDtypeStruct((B * KVH, Skvp, Dp), k.dtype),
            jax.ShapeDtypeStruct((B * KVH, Skvp, Dp), v.dtype),
        ),
        grid=(B * KVH, nk, G * nq),
        in_specs=[
            pl.BlockSpec((1, block_q, Dp), q_map2),
            pl.BlockSpec((1, block_k, Dp), kv_map2),
            pl.BlockSpec((1, block_k, Dp), kv_map2),
            pl.BlockSpec((1, block_q, Dp), q_map2),
            pl.BlockSpec((1, 1, block_q), r_map2),
            pl.BlockSpec((1, 1, block_q), r_map2),
        ],
        out_specs=(
            pl.BlockSpec((1, block_k, Dp), kv_map2),
            pl.BlockSpec((1, block_k, Dp), kv_map2),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, Dp), jnp.float32),
            pltpu.VMEM((block_k, Dp), jnp.float32),
        ],
        interpret=interpret,
        name="flash_attention_pallas_bwd_dkv",
    )(qf, kf, vf, dof, lsef, deltaf)

    def unflat(x, S, NH):
        return jnp.swapaxes(x[:, :S, :D].reshape(B, NH, S, D), 1, 2)

    return unflat(dq, Sq, H), unflat(dk, Skv, KVH), unflat(dv, Skv, KVH)
