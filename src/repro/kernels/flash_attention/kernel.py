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
  * Causal/sliding-window masking is applied with absolute-position iota
    comparison inside the block. Fully-masked blocks contribute zeros.
  * Backward is flash-attention-2 style: the forward emits LSE; a dQ
    kernel accumulates over KV blocks, and a dK/dV kernel accumulates over
    (query-head-in-group x q-block) pairs via its minor grid dimension —
    GQA's head-group reduction becomes grid scheduling instead of atomics.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import dispatch

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int, block_q: int,
               block_k: int, q_offset: int, kv_len: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)                  # (bk, D)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))   # (bq, bk)

    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    mask = kpos < kv_len                               # padding mask
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new)
    # fully-masked rows: m_new stays NEG_INF -> p would be exp(0)=1; zero them
    p = jnp.where(m_new > NEG_INF / 2, p, 0.0)
    alpha = jnp.where(m_prev > NEG_INF / 2, alpha, 0.0)

    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot(p, v)
    m_ref[...] = m_new

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


def _layout(q, k, v, block_q, block_k, interpret):
    """Flatten to (B*H, S, D) batch-head major, pad to block/lane multiples."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    Dp = max(128, (D + 127) // 128 * 128) if not interpret else D
    block_q = min(block_q, max(Sq, 8))
    block_k = min(block_k, max(Skv, 8))
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
    grid = (B * H, Sqp // block_q, Skvp // block_k)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def lse_map(bh, qi, ki):
        return (bh, 0, qi)

    def kv_map(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KVH + h // G, ki, 0)

    out, lse = pl.pallas_call(
        functools.partial(
            _fa_kernel, scale=scale, causal=causal, window=window,
            block_q=block_q, block_k=block_k, q_offset=q_offset, kv_len=Skv),
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


def _mask(qi, ki, block_q, block_k, q_offset, q_len, kv_len, causal, window):
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + q_offset
    kpos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    m = (kpos < kv_len) & (qpos - q_offset < q_len)
    if causal:
        m &= kpos <= qpos
    if window > 0:
        m &= kpos > qpos - window
    return m


def _fa_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      dq_ref, acc_ref, *, scale, causal, window, block_q,
                      block_k, q_offset, q_len, kv_len):
    qi, ki = pl.program_id(1), pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]                              # (block_q,)
    delta = delta_ref[0, 0]                          # (block_q,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    mask = _mask(qi, ki, block_q, block_k, q_offset, q_len, kv_len, causal,
                 window)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])                    # (bq, bk)
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta[:, None])
    acc_ref[...] += jax.lax.dot(ds, k) * scale

    @pl.when(ki == pl.num_programs(2) - 1)
    def _():
        dq_ref[0, ...] = acc_ref[...].astype(dq_ref.dtype)


def _fa_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                       dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                       window, block_q, block_k, q_offset, q_len, kv_len,
                       nq: int):
    ki, gq = pl.program_id(1), pl.program_id(2)
    qi = gq % nq

    @pl.when(gq == 0)
    def _():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    q = q_ref[0].astype(jnp.float32) * scale
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, 0]
    delta = delta_ref[0, 0]

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))
    mask = _mask(qi, ki, block_q, block_k, q_offset, q_len, kv_len, causal,
                 window)
    s = jnp.where(mask, s, NEG_INF)
    p = jnp.exp(s - lse[:, None])                    # (bq, bk)
    dv_acc[...] += jax.lax.dot_general(p, do, (((0,), (0,)), ((), ())))
    dp = jax.lax.dot_general(do, v, (((1,), (1,)), ((), ())))
    ds = p * (dp - delta[:, None])                   # (bq, bk)
    dk_acc[...] += jax.lax.dot_general(ds, q, (((0,), (0,)), ((), ())))

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
    nq, nk = Sqp // block_q, Skvp // block_k

    kw = dict(scale=scale, causal=causal, window=window, block_q=block_q,
              block_k=block_k, q_offset=q_offset, q_len=Sq, kv_len=Skv)

    def q_map(bh, qi, ki):
        return (bh, qi, 0)

    def r_map(bh, qi, ki):
        return (bh, 0, qi)

    def kv_map(bh, qi, ki):
        b, h = bh // H, bh % H
        return (b * KVH + h // G, ki, 0)

    dq = pl.pallas_call(
        functools.partial(_fa_bwd_dq_kernel, **kw),
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

    # dK/dV: grid minor dim runs over (g, qi) pairs of this kv head
    def q_map2(bkv, ki, gq):
        b, hkv = bkv // KVH, bkv % KVH
        return (b * H + hkv * G + gq // nq, gq % nq, 0)

    def r_map2(bkv, ki, gq):
        b, hkv = bkv // KVH, bkv % KVH
        return (b * H + hkv * G + gq // nq, 0, gq % nq)

    def kv_map2(bkv, ki, gq):
        return (bkv, ki, 0)

    dk, dv = pl.pallas_call(
        functools.partial(_fa_bwd_dkv_kernel, **kw, nq=nq),
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
