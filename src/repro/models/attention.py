"""Attention variants: GQA (+bias, sliding window, cross), MLA (latent KV).

Three execution modes per variant:
  * train  — full sequence, no cache returned
  * prefill — full sequence, returns the KV cache
  * decode — one token against a cache (full or circular sliding-window)

Caches are plain dicts of arrays so they stack cleanly across scanned layers.
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.kernels.flash_attention import flash_attention
from repro.models.layers import apply_norm, apply_rope, dense_init, mdot, rope_cos_sin

# ---------------------------------------------------------------------------
# GQA
# ---------------------------------------------------------------------------


def init_gqa(key, cfg: ModelConfig, cross: bool = False):
    d, H, KVH, Dh = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, H * Dh)),
        "wk": dense_init(ks[1], (d, KVH * Dh)),
        "wv": dense_init(ks[2], (d, KVH * Dh)),
        "wo": dense_init(ks[3], (H * Dh, d), fan_in=H * Dh),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((H * Dh,), jnp.float32)
        p["bk"] = jnp.zeros((KVH * Dh,), jnp.float32)
        p["bv"] = jnp.zeros((KVH * Dh,), jnp.float32)
    return p


def _qkv(params, x, kv_x, cfg: ModelConfig, dtype):
    B, S, _ = x.shape
    Skv = kv_x.shape[1]
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = mdot(x, params["wq"], dtype)
    k = mdot(kv_x, params["wk"], dtype)
    v = mdot(kv_x, params["wv"], dtype)
    if cfg.qkv_bias:
        q = q + params["bq"].astype(dtype)
        k = k + params["bk"].astype(dtype)
        v = v + params["bv"].astype(dtype)
    return (q.reshape(B, S, H, Dh), k.reshape(B, Skv, KVH, Dh),
            v.reshape(B, Skv, KVH, Dh))


def _rope(cfg: ModelConfig, q, k, positions, kv_positions=None):
    if positions is None:
        return q, k
    cos_q, sin_q = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                cfg.mrope_sections)
    q = apply_rope(q, cos_q, sin_q)
    if k is not None:
        kp = positions if kv_positions is None else kv_positions
        cos_k, sin_k = rope_cos_sin(kp, cfg.head_dim, cfg.rope_theta,
                                    cfg.mrope_sections)
        k = apply_rope(k, cos_k, sin_k)
    return q, k


def gqa_forward(params, x, cfg: ModelConfig, *, positions=None, window: int = 0,
                causal: bool = True, cross_x=None, return_cache: bool = False,
                length=None):
    """Train/prefill path. x: (B,S,d). cross_x: encoder output for cross-attn
    (no rope, no mask). Returns out or (out, cache). ``length``: optional
    scalar count of REAL tokens when x is right-padded to a prefill bucket —
    window caches then arrange slots by real positions (pad rows excluded)."""
    dtype = x.dtype
    kv_src = cross_x if cross_x is not None else x
    q, k, v = _qkv(params, x, kv_src, cfg, dtype)
    if cross_x is None:
        q, k = _rope(cfg, q, k, positions)
    out = flash_attention(
        q, k, v, causal=causal and cross_x is None, window=window,
        scale=cfg.attention_scale or None, chunk=cfg.attention_chunk,
        impl=cfg.attention_impl, design=cfg.attention_design or None)
    B, S = x.shape[:2]
    out = mdot(out.reshape(B, S, -1), params["wo"], dtype)
    if not return_cache:
        return out
    if window > 0:
        k = _window_slots(k, window, length)
        v = _window_slots(v, window, length)
    return out, _maybe_quant_cache(cfg, k, v)


# ---------------------------------------------------------------------------
# int8 KV cache (symmetric per-(token, head) quantization)
# ---------------------------------------------------------------------------


def quantize_kv(x):
    """x: (..., Dh) -> (int8 values, f32 scale with trailing 1-dim)."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale), -127, 127)
    return q.astype(jnp.int8), scale


def dequantize_kv(q, scale, dtype):
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _maybe_quant_cache(cfg: ModelConfig, k, v):
    if cfg.kv_cache_dtype != "int8":
        return {"k": k, "v": v}
    kq, ks = quantize_kv(k)
    vq, vs = quantize_kv(v)
    return {"k": kq, "k_scale": ks, "v": vq, "v_scale": vs}


def _cache_kv(cfg: ModelConfig, cache, dtype):
    if "k_scale" in cache:
        return (dequantize_kv(cache["k"], cache["k_scale"], dtype),
                dequantize_kv(cache["v"], cache["v_scale"], dtype))
    return cache["k"], cache["v"]


def _window_slots(kv, window: int, length=None):
    """Arrange the last `window` entries into circular slot order.
    kv: (B,S,KVH,Dh) -> (B,window,KVH,Dh) where slot i holds the latest
    position p <= S-1 with p ≡ i (mod window), or zeros if none.
    ``length``: optional (traced) count of real tokens — rows past it are
    prefill-bucket padding and must not land in any slot."""
    B, S, KVH, Dh = kv.shape
    if length is not None:
        # dynamic form of the same rule, p = latest real pos ≡ i (mod W)
        i = jnp.arange(window)
        p = (length - 1) - jnp.mod(length - 1 - i, window)
        rows = jnp.take(kv, jnp.clip(p, 0, S - 1), axis=1)
        return jnp.where((p >= 0)[None, :, None, None], rows,
                         jnp.zeros_like(rows))
    if S <= window:
        return jnp.pad(kv, ((0, 0), (0, window - S), (0, 0), (0, 0)))
    last = kv[:, S - window:]                     # positions S-window .. S-1
    slots = (jnp.arange(S - window, S)) % window
    out = jnp.zeros((B, window, KVH, Dh), kv.dtype)
    return out.at[:, slots].set(last)


def _slot_positions(pos, cache_len: int, window: int):
    """Absolute position stored in each slot of a (possibly circular) cache
    after the token at `pos` has been written. -1 = empty.
    pos: scalar or (B,) — returns (L,) or (B, L) accordingly (per-request
    positions enable continuous batching)."""
    i = jnp.arange(cache_len)
    pos = jnp.asarray(pos)
    if pos.ndim == 1:
        i = i[None, :]
        pos = pos[:, None]
    if window > 0:
        p = pos - jnp.mod(pos - i, cache_len)
        return jnp.where(p >= 0, p, -1)
    return jnp.where(i <= pos, i, -1)


def gqa_decode(params, x, cache, pos, cfg: ModelConfig, *, window: int = 0,
               positions=None, cross: bool = False, use_rope: bool = True):
    """One-token decode. x: (B,1,d); cache{k,v}: (B,L,KVH,Dh); pos: scalar
    absolute position. positions: optional (B,1) or (B,3,1) for M-RoPE.
    Returns (out, new_cache)."""
    dtype = x.dtype
    B = x.shape[0]
    H, KVH, Dh = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    if cross:
        # cross-attention cache is static (encoder kv); only compute q
        q = mdot(x, params["wq"], dtype)
        if cfg.qkv_bias:
            q = q + params["bq"].astype(dtype)
        q = q.reshape(B, 1, H, Dh)
        k, v = _cache_kv(cfg, cache, dtype)
        out = _cache_attend(q, k, v, kpos=None, scale=cfg.attention_scale)
        return mdot(out.reshape(B, 1, -1), params["wo"], dtype), cache

    q, k_new, v_new = _qkv(params, x, x, cfg, dtype)
    pos = jnp.asarray(pos)
    vec = pos.ndim == 1                     # per-request positions
    if use_rope:
        if positions is None:
            positions = (pos[:, None] if vec
                         else jnp.full((B, 1), pos)).astype(jnp.int32)
        q, k_new = _rope(cfg, q, k_new, positions)

    L = cache["k"].shape[1]
    slot = jnp.mod(pos, L) if window > 0 else pos

    def upd(buf, new):
        if vec:
            return buf.at[jnp.arange(B), slot].set(
                new[:, 0].astype(buf.dtype))
        if window == 0:
            return jax.lax.dynamic_update_slice_in_dim(
                buf, new.astype(buf.dtype), slot, axis=1)
        return buf.at[:, slot].set(new[:, 0].astype(buf.dtype))

    if "k_scale" in cache:      # int8 cache: quantize the new token
        knq, kns = quantize_kv(k_new)
        vnq, vns = quantize_kv(v_new)
        new_cache = {"k": upd(cache["k"], knq),
                     "k_scale": upd(cache["k_scale"], kns),
                     "v": upd(cache["v"], vnq),
                     "v_scale": upd(cache["v_scale"], vns)}
    else:
        new_cache = {"k": upd(cache["k"], k_new), "v": upd(cache["v"], v_new)}
    k, v = _cache_kv(cfg, new_cache, dtype)

    kpos = _slot_positions(pos, L, window)
    out = _cache_attend(q, k, v, kpos=kpos, scale=cfg.attention_scale)
    out = mdot(out.reshape(B, 1, -1), params["wo"], dtype)
    return out, new_cache


def _cache_attend(q, k, v, kpos, scale: float = 0.0):
    """Single-query attention over a cache. q: (B,1,H,Dh); k/v: (B,L,KVH,Dh);
    kpos: (L,) or per-request (B,L) absolute positions, or None (cross).
    ``scale``: the softmax scale; 0 -> 1/sqrt(Dh)."""
    B, _, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    scale = scale or Dh ** -0.5
    qf = (q.astype(jnp.float32) * scale).reshape(B, KVH, G, Dh)
    s = jnp.einsum("bhgd,blhd->bhgl", qf, k.astype(jnp.float32))
    if kpos is not None:
        kp = kpos if kpos.ndim == 2 else kpos[None, :]
        s = jnp.where(kp[:, None, None, :] >= 0, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhgl,blhd->bhgd", p, v.astype(jnp.float32))
    return o.reshape(B, 1, H * Dh).astype(q.dtype)


def gqa_empty_cache(cfg: ModelConfig, batch: int, cache_len: int, window: int,
                    dtype):
    L = min(cache_len, window) if window > 0 else cache_len
    KVH, Dh = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        zq = jnp.zeros((batch, L, KVH, Dh), jnp.int8)
        zs = jnp.full((batch, L, KVH, 1), 1e-8 / 127.0, jnp.float32)
        return {"k": zq, "k_scale": zs, "v": zq, "v_scale": zs}
    z = jnp.zeros((batch, L, KVH, Dh), dtype)
    return {"k": z, "v": z}


# ---------------------------------------------------------------------------
# paged KV pool (vLLM-style block tables, JAX static shapes)
# ---------------------------------------------------------------------------


def gqa_empty_page_pool(cfg: ModelConfig, n_pages: int, page_size: int,
                        dtype):
    """Global device-resident KV page pool shared by every slot:
    ``(n_pages, page_size, KVH, Dh)`` per leaf. Page 0 is RESERVED as the
    null page — block-table entries of unallocated regions (and of freed
    slots) point at it, so out-of-extent cache writes land in garbage that
    the position mask never admits."""
    KVH, Dh = cfg.n_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        zq = jnp.zeros((n_pages, page_size, KVH, Dh), jnp.int8)
        zs = jnp.full((n_pages, page_size, KVH, 1), 1e-8 / 127.0,
                      jnp.float32)
        return {"k": zq, "k_scale": zs, "v": zq, "v_scale": zs}
    z = jnp.zeros((n_pages, page_size, KVH, Dh), dtype)
    return {"k": z, "v": z}


def gqa_decode_paged(params, x, cache, pos, block_tables, cfg: ModelConfig,
                     *, positions=None, use_rope: bool = True):
    """One-token decode against a paged KV pool.

    cache leaves: ``(n_pages, page_size, KVH, Dh)`` global pool;
    ``block_tables``: (B, M) int32 page ids per slot (entry 0 = the
    reserved null page); pos: (B,) per-request absolute positions.

    The new token writes to ``pool[bt[b, pos//P], pos % P]`` and attention
    gathers each slot's pages back into a contiguous (B, M*P) view. Rows
    <= pos of that view hold exactly the values a dense per-slot cache
    would (the engine scatters prefill rows page-aligned), and rows > pos
    are masked to an exact-zero softmax contribution — so greedy tokens
    match the dense layout bitwise. Returns (out, new_cache)."""
    dtype = x.dtype
    B = x.shape[0]
    q, k_new, v_new = _qkv(params, x, x, cfg, dtype)
    pos = jnp.asarray(pos)
    if use_rope:
        if positions is None:
            positions = pos[:, None].astype(jnp.int32)
        q, k_new = _rope(cfg, q, k_new, positions)

    P = cache["k"].shape[1]                       # page_size
    M = block_tables.shape[1]
    page = block_tables[jnp.arange(B), pos // P]  # (B,) write page per slot
    off = jnp.mod(pos, P)

    def upd(buf, new):
        # each active slot owns its write page exclusively; frozen slots
        # point at their own pages or the null page — never another slot's
        return buf.at[page, off].set(new[:, 0].astype(buf.dtype))

    if "k_scale" in cache:      # int8 pool: quantize the new token
        knq, kns = quantize_kv(k_new)
        vnq, vns = quantize_kv(v_new)
        new_cache = {"k": upd(cache["k"], knq),
                     "k_scale": upd(cache["k_scale"], kns),
                     "v": upd(cache["v"], vnq),
                     "v_scale": upd(cache["v_scale"], vns)}
    else:
        new_cache = {"k": upd(cache["k"], k_new),
                     "v": upd(cache["v"], v_new)}

    def gather(buf):
        g = jnp.take(buf, block_tables, axis=0)   # (B, M, P, ...)
        return g.reshape((B, M * P) + buf.shape[2:])

    k, v = _cache_kv(cfg, {kk: gather(vv) for kk, vv in new_cache.items()},
                     dtype)
    kpos = _slot_positions(pos, M * P, 0)
    out = _cache_attend(q, k, v, kpos=kpos, scale=cfg.attention_scale)
    out = mdot(out.reshape(B, 1, -1), params["wo"], dtype)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLA (multi-head latent attention, MiniCPM3/DeepSeek-V2 style)
# ---------------------------------------------------------------------------


def init_mla(key, cfg: ModelConfig):
    m = cfg.mla
    d, H = cfg.d_model, cfg.n_heads
    ks = jax.random.split(key, 7)
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    return {
        "wq_a": dense_init(ks[0], (d, m.q_lora_rank)),
        "q_norm": {"scale": jnp.ones((m.q_lora_rank,), jnp.float32)},
        "wq_b": dense_init(ks[1], (m.q_lora_rank, H * qh), fan_in=m.q_lora_rank),
        "wkv_a": dense_init(ks[2], (d, m.kv_lora_rank + m.qk_rope_head_dim)),
        "kv_norm": {"scale": jnp.ones((m.kv_lora_rank,), jnp.float32)},
        "wk_b": dense_init(ks[3], (m.kv_lora_rank, H * m.qk_nope_head_dim),
                           fan_in=m.kv_lora_rank),
        "wv_b": dense_init(ks[4], (m.kv_lora_rank, H * m.v_head_dim),
                           fan_in=m.kv_lora_rank),
        "wo": dense_init(ks[5], (H * m.v_head_dim, d), fan_in=H * m.v_head_dim),
    }


def _mla_q(params, x, cfg, positions, dtype):
    m = cfg.mla
    B, S, _ = x.shape
    H = cfg.n_heads
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    q_lat = apply_norm(params["q_norm"], mdot(x, params["wq_a"], dtype),
                       "rmsnorm", cfg.norm_eps)
    q = mdot(q_lat, params["wq_b"], dtype).reshape(B, S, H, qh)
    q_nope, q_rope = q[..., :m.qk_nope_head_dim], q[..., m.qk_nope_head_dim:]
    if positions is not None:
        cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)
        q_rope = apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _mla_latent(params, x, cfg, positions, dtype):
    m = cfg.mla
    kv = mdot(x, params["wkv_a"], dtype)
    c_kv, k_rope = kv[..., :m.kv_lora_rank], kv[..., m.kv_lora_rank:]
    c_kv = apply_norm(params["kv_norm"], c_kv, "rmsnorm", cfg.norm_eps)
    k_rope = k_rope[:, :, None, :]                       # (B,S,1,rope)
    if positions is not None:
        cos, sin = rope_cos_sin(positions, m.qk_rope_head_dim, cfg.rope_theta)
        k_rope = apply_rope(k_rope, cos, sin)
    return c_kv, k_rope[:, :, 0, :]


def mla_forward(params, x, cfg: ModelConfig, *, positions=None,
                return_cache: bool = False):
    """Expanded (train/prefill) MLA: materialize per-head K/V, flash attn."""
    m = cfg.mla
    dtype = x.dtype
    B, S, _ = x.shape
    H = cfg.n_heads
    q_nope, q_rope = _mla_q(params, x, cfg, positions, dtype)
    c_kv, k_rope = _mla_latent(params, x, cfg, positions, dtype)

    k_nope = mdot(c_kv, params["wk_b"], dtype).reshape(B, S, H, m.qk_nope_head_dim)
    v = mdot(c_kv, params["wv_b"], dtype).reshape(B, S, H, m.v_head_dim)

    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (B, S, H, m.qk_rope_head_dim))], axis=-1)
    # pad v head dim up to qk head dim for the shared kernel, slice after
    qh = q.shape[-1]
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qh - m.v_head_dim)))
    out = flash_attention(q, k, vpad, causal=True, chunk=cfg.attention_chunk,
                          impl=cfg.attention_impl, scale=qh ** -0.5,
                          design=cfg.attention_design or None)
    out = out[..., :m.v_head_dim].reshape(B, S, -1)
    out = mdot(out, params["wo"], dtype)
    if not return_cache:
        return out
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_decode(params, x, cache, pos, cfg: ModelConfig, positions=None):
    """Absorbed-latent decode: attention runs in the kv_lora_rank space —
    the cache is (B, L, r + rope) instead of per-head K/V (MLA's serving win).
    """
    m = cfg.mla
    dtype = x.dtype
    B = x.shape[0]
    H = cfg.n_heads
    pos = jnp.asarray(pos)
    vec = pos.ndim == 1
    if positions is None:
        positions = (pos[:, None] if vec
                     else jnp.full((B, 1), pos)).astype(jnp.int32)
    q_nope, q_rope = _mla_q(params, x, cfg, positions, dtype)   # (B,1,H,·)
    c_new, kr_new = _mla_latent(params, x, cfg, positions, dtype)

    if vec:
        rows = jnp.arange(B)
        c_kv = cache["c_kv"].at[rows, pos].set(
            c_new[:, 0].astype(cache["c_kv"].dtype))
        k_rope = cache["k_rope"].at[rows, pos].set(
            kr_new[:, 0].astype(cache["k_rope"].dtype))
    else:
        c_kv = jax.lax.dynamic_update_slice_in_dim(
            cache["c_kv"], c_new.astype(cache["c_kv"].dtype), pos, axis=1)
        k_rope = jax.lax.dynamic_update_slice_in_dim(
            cache["k_rope"], kr_new.astype(cache["k_rope"].dtype), pos,
            axis=1)

    L = c_kv.shape[1]
    wk_b = params["wk_b"].astype(dtype).reshape(m.kv_lora_rank, H, m.qk_nope_head_dim)
    # absorb: q' = q_nope @ W_k^T per head -> latent space
    q_lat = jnp.einsum("bhd,rhd->bhr", q_nope[:, 0], wk_b)       # (B,H,r)
    s = jnp.einsum("bhr,blr->bhl", q_lat, c_kv.astype(dtype))
    s = s + jnp.einsum("bhd,bld->bhl", q_rope[:, 0], k_rope.astype(dtype))
    qh = m.qk_nope_head_dim + m.qk_rope_head_dim
    s = s.astype(jnp.float32) * (qh ** -0.5)
    limit = pos[:, None, None] if vec else pos
    s = jnp.where(jnp.arange(L)[None, None, :] <= limit, s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    o_lat = jnp.einsum("bhl,blr->bhr", p.astype(dtype), c_kv.astype(dtype))
    wv_b = params["wv_b"].astype(dtype).reshape(m.kv_lora_rank, H, m.v_head_dim)
    o = jnp.einsum("bhr,rhd->bhd", o_lat, wv_b).reshape(B, 1, -1)
    out = mdot(o, params["wo"], dtype)
    return out, {"c_kv": c_kv, "k_rope": k_rope}


def mla_empty_cache(cfg: ModelConfig, batch: int, cache_len: int, dtype):
    m = cfg.mla
    return {
        "c_kv": jnp.zeros((batch, cache_len, m.kv_lora_rank), dtype),
        "k_rope": jnp.zeros((batch, cache_len, m.qk_rope_head_dim), dtype),
    }
