"""Public attention op.

``impl="reference"``: blockwise pure-jnp flash formulation (lax.scan over KV
chunks, online softmax). This is the path used for lowering/dry-run and CPU
execution — it has the same O(S) memory behaviour as the kernel, so compiled
HLO bytes reflect the flash algorithm rather than a materialized QK^T.

``impl="pallas"``: the compiled kernel for the live backend — the Mosaic
program (kernel.py) on TPU, the Triton program (kernel_gpu.py) on GPU;
``impl="mosaic"``/``impl="triton"`` force a specific lowering (interpreter
off its native backend — how CPU CI equivalence-tests both). Gradients via
custom_vjp: forward runs the kernel, backward runs the true flash backward
kernels with the forward's LSE.

``impl="naive"``: the oracle (tests only).

``impl="auto"`` (the config default): backend-resolved — compiled Mosaic on
TPU, compiled Triton on GPU, the blockwise reference on CPU
(repro.kernels.dispatch); the resolved design point (block sizes,
num_warps/num_stages) comes from the persisted tuning cache, or from the
``design`` argument when a caller pins one; a Mosaic call on the TPU with
neither takes the tile measured on the chip (``_mosaic_blocks``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro import obs
from repro.kernels import dispatch
from repro.kernels.flash_attention import ref as _ref
from repro.kernels.flash_attention.kernel import (
    flash_attention_pallas, flash_attention_pallas_bwd,
    flash_attention_pallas_fwd, tile_schedule,
)
from repro.kernels.flash_attention.kernel_gpu import (
    flash_attention_triton, flash_attention_triton_bwd,
    flash_attention_triton_fwd,
)
from repro.kernels.tuning import (DEFAULT_DESIGN, TPU_FLASH_TILE_AREA,
                                  TPU_FLASH_TILES, flash_tile)


def _blockwise_reference(q, k, v, *, causal, window, scale, q_offset, chunk):
    """Online-softmax attention, chunked over KV; pure jnp, differentiable."""
    B, Sq, H, D = q.shape
    _, Skv, KVH, _ = k.shape
    G = H // KVH
    scale = scale if scale is not None else D ** -0.5
    chunk = min(chunk, Skv)
    # pad Skv to a chunk multiple
    pad = (-Skv) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    n_chunks = (Skv + pad) // chunk

    # keep Q/K/V in their storage dtype (bf16 on TPU) and accumulate the
    # dots in f32 via preferred_element_type — halves the attention HBM
    # traffic vs upcasting inputs to f32 (§Perf iter 4); running stats and
    # the softmax stay f32 for stability.
    qf = (q * jnp.asarray(scale, q.dtype)).reshape(B, Sq, KVH, G, D)
    kc = jnp.moveaxis(k.reshape(B, n_chunks, chunk, KVH, D), 1, 0)
    vc = jnp.moveaxis(v.reshape(B, n_chunks, chunk, KVH, D), 1, 0)

    qpos = jnp.arange(Sq) + q_offset

    def step(carry, xs):
        m, l, acc = carry
        kb, vb, ci = xs
        s = jnp.einsum("bqhgd,bkhd->bqhgk", qf, kb,
                       preferred_element_type=jnp.float32)
        kpos = ci * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < Skv
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(mask[None, :, None, None, :], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        p = jnp.where(m_new > -1e29, p, 0.0)
        alpha = jnp.where(m > -1e29, jnp.exp(m - m_new), 0.0)
        l = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha[..., 0][..., None] + jnp.einsum(
            "bqhgk,bkhd->bqhgd", p.astype(v.dtype), vb,
            preferred_element_type=jnp.float32)
        return (m_new, l, acc), None

    m0 = jnp.full((B, Sq, KVH, G, 1), -1e30, jnp.float32)
    l0 = jnp.zeros((B, Sq, KVH, G, 1), jnp.float32)
    a0 = jnp.zeros((B, Sq, KVH, G, D), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0), (kc, vc, jnp.arange(n_chunks)))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).reshape(B, Sq, H, D)
    return out.astype(q.dtype)


# nondiff args (all static/hashable: bools, ints, float-or-None, the
# (block_q, block_k) pair) come first in the bwd signature, per the argnums
# convention.
@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _pallas_attention(q, k, v, causal, window, scale, q_offset, blocks,
                      interpret):
    return flash_attention_pallas(q, k, v, causal=causal, window=window,
                                  scale=scale, q_offset=q_offset,
                                  block_q=blocks[0], block_k=blocks[1],
                                  interpret=interpret)


def _mosaic_blocks(d, pinned: bool, sq: int, skv: int, head_dim: int):
    """(block_q, block_k) for the Mosaic kernel. A pinned design or a
    tuning-cache entry is taken as given (a 0 field means the kernel
    default); a TPU call with neither takes, per side, the largest tile up
    to the measured TPU tile (less for heads wider than 256) that divides
    its length rounded up to 128."""
    if d.backend == "tpu" and not (pinned or d.cache_hit):
        cap = TPU_FLASH_TILE_AREA // max(256, -(-head_dim // 128) * 128)
        bq, bk = (min(t, cap) for t in TPU_FLASH_TILES)
        return flash_tile(sq, bq), flash_tile(skv, bk)
    dflt = DEFAULT_DESIGN["flash_attention"]
    return d.design.block_q or dflt.block_q, d.design.block_k or dflt.block_k


def _pallas_fwd(q, k, v, causal, window, scale, q_offset, blocks, interpret):
    out, lse = flash_attention_pallas_fwd(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret)
    return out, (q, k, v, out, lse)


def _pallas_bwd(causal, window, scale, q_offset, blocks, interpret, res, g):
    # true flash backward (Pallas dQ + dK/dV kernels, LSE from forward)
    q, k, v, out, lse = res
    return flash_attention_pallas_bwd(
        q, k, v, out, lse, g, causal=causal, window=window, scale=scale,
        q_offset=q_offset, block_q=blocks[0], block_k=blocks[1],
        interpret=interpret)


_pallas_attention.defvjp(_pallas_fwd, _pallas_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _triton_attention(q, k, v, causal, window, scale, q_offset, design,
                      interpret):
    return flash_attention_triton(q, k, v, causal=causal, window=window,
                                  scale=scale, q_offset=q_offset,
                                  design=design, interpret=interpret)


def _triton_fwd(q, k, v, causal, window, scale, q_offset, design, interpret):
    out, lse = flash_attention_triton_fwd(
        q, k, v, causal=causal, window=window, scale=scale,
        q_offset=q_offset, design=design, interpret=interpret)
    return out, (q, k, v, out, lse)


def _triton_bwd(causal, window, scale, q_offset, design, interpret, res, g):
    q, k, v, out, lse = res
    return flash_attention_triton_bwd(
        q, k, v, out, lse, g, causal=causal, window=window, scale=scale,
        q_offset=q_offset, design=design, interpret=interpret)


_triton_attention.defvjp(_triton_fwd, _triton_bwd)


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    scale: float | None = None, q_offset: int = 0,
                    chunk: int = 512, impl: str = "auto", design=None):
    """GQA flash attention. q: (B,Sq,H,D); k,v: (B,Skv,KVH,D).
    ``design`` pins a tuning design point (DesignPoint or 4-tuple);
    default None consults the tuning cache for the resolved backend."""
    d = dispatch.resolve(impl, kernel="flash_attention",
                         shape=(k.shape[1], q.shape[-1]), design=design)
    if d.impl == "naive":
        return _ref.attention_ref(q, k, v, causal=causal, window=window,
                                  scale=scale, q_offset=q_offset)
    if d.impl == "pallas" and d.variant == "triton":
        return _triton_attention(q, k, v, causal, window, scale, q_offset,
                                 d.design, d.interpret)
    if d.impl == "pallas":
        blocks = _mosaic_blocks(d, design is not None, q.shape[1],
                                k.shape[1], q.shape[-1])
        tiles, live = tile_schedule(
            q.shape[1], k.shape[1], causal=causal, window=window,
            q_offset=q_offset, block_q=blocks[0], block_k=blocks[1])
        obs.count("flash_attention.tiles", tiles)
        obs.count("flash_attention.tiles_live", live)
        return _pallas_attention(q, k, v, causal, window, scale, q_offset,
                                 blocks, d.interpret)
    return _blockwise_reference(q, k, v, causal=causal, window=window,
                                scale=scale, q_offset=q_offset, chunk=chunk)
