"""Model assembly for all assigned architectures.

Layers are grouped into *pattern units* so heterogeneous stacks lower as a
single ``lax.scan`` over stacked parameters:

  dense/moe/vlm : unit = 1 layer  (gemma3: unit = 5 local + 1 global)
  ssm           : unit = 1 mamba layer
  hybrid        : unit = shared_attn_every mamba layers, with the ONE shared
                  attention block (zamba2) applied before each unit
  audio         : separate encoder / decoder stacks (whisper)

Remaining layers (n_layers % unit_len) form an unrolled tail. Within a unit
the per-position layer kind (local/global window, moe, mamba) is static
Python, so a unit body is trace-time specialized; across units everything is
structurally identical, which keeps compiled HLO size O(unit) instead of
O(n_layers) — essential for the 94-layer MoE dry-run at 512 devices.

Caches are dicts keyed by position-in-unit (string), stacked across units on
the leading axis, so sliding-window layers can hold (window)-sized caches
next to full-length global caches in the same scan.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.dist.sharding import logical_constraint
from repro.models import attention as attn, mamba2, moe
from repro.models.layers import (
    apply_mlp, apply_norm, dense_init, embed_init, init_mlp, init_norm, mdot,
    sinusoidal_embedding,
)


@dataclasses.dataclass(frozen=True)
class LayerKind:
    block: str          # "attn" | "mamba"
    window: int = 0     # sliding window for attn (0 = full)
    use_moe: bool = False
    cross: bool = False  # adds cross-attention (whisper decoder)


def _tree_index(tree, i):
    return jax.tree_util.tree_map(lambda a: a[i], tree)


def _tree_stack(trees):
    return jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *trees)


class Model:
    """Functional model: init/apply/prefill/decode."""

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.dtype = jnp.dtype(cfg.dtype)
        self.unit_kinds, self.n_units, self.tail_kinds = self._plan(cfg)
        self.use_rope = cfg.family != "audio"

    # ------------------------------------------------------------------
    # layer plan
    # ------------------------------------------------------------------

    @staticmethod
    def _plan(cfg: ModelConfig) -> Tuple[List[LayerKind], int, List[LayerKind]]:
        if cfg.family == "ssm":
            unit = [LayerKind("mamba")]
        elif cfg.family == "hybrid":
            unit = [LayerKind("mamba")] * cfg.shared_attn_every
        elif cfg.family == "audio":
            unit = [LayerKind("attn", cross=True)]
        elif cfg.local_global_pattern != (0, 0):
            loc, glob = cfg.local_global_pattern
            unit = ([LayerKind("attn", window=cfg.sliding_window)] * loc
                    + [LayerKind("attn")] * glob)
        else:
            unit = [LayerKind("attn", window=cfg.sliding_window,
                              use_moe=cfg.family == "moe")]
        if cfg.family == "moe":
            unit = [dataclasses.replace(k, use_moe=True) for k in unit]
        n_units, rem = divmod(cfg.n_layers, len(unit))
        tail = unit[:rem]
        return unit, n_units, tail

    # ------------------------------------------------------------------
    # paged-cache capability
    # ------------------------------------------------------------------

    def pageable(self, kind: LayerKind) -> bool:
        """Whether a layer's KV cache can live in a paged page pool:
        full-attention GQA self-attention only. Sliding-window caches are
        already O(window), SSM states are O(1), and MLA/cross caches keep
        their dense layout behind this capability gate."""
        return (kind.block == "attn" and kind.window == 0
                and not kind.cross and self.cfg.attention == "gqa")

    @property
    def has_pageable(self) -> bool:
        """True if any layer can use a paged KV pool (the serving engine's
        ``kv_layout="auto"`` resolves to paged exactly then)."""
        kinds = list(self.unit_kinds) + list(self.tail_kinds)
        if self.cfg.family == "hybrid":
            kinds.append(LayerKind("attn"))
        return any(self.pageable(k) for k in kinds)

    # ------------------------------------------------------------------
    # init
    # ------------------------------------------------------------------

    def _init_block(self, key, kind: LayerKind):
        cfg = self.cfg
        ks = jax.random.split(key, 6)
        if kind.block == "mamba":
            return {"ln1": init_norm(ks[0], cfg.d_model, cfg.norm),
                    "mamba": mamba2.init_mamba(ks[1], cfg)}
        p = {"ln1": init_norm(ks[0], cfg.d_model, cfg.norm),
             "ln2": init_norm(ks[1], cfg.d_model, cfg.norm)}
        if cfg.attention == "mla":
            p["attn"] = attn.init_mla(ks[2], cfg)
        else:
            p["attn"] = attn.init_gqa(ks[2], cfg)
        if kind.cross and cfg.is_encoder_decoder:
            p["lnx"] = init_norm(ks[3], cfg.d_model, cfg.norm)
            p["xattn"] = attn.init_gqa(ks[4], cfg, cross=True)
        if kind.use_moe:
            p["moe"] = moe.init_moe(ks[5], cfg)
        else:
            p["mlp"] = init_mlp(ks[5], cfg.d_model, cfg.d_ff)
        return p

    def init(self, key) -> Dict[str, Any]:
        cfg = self.cfg
        keys = jax.random.split(key, 8)
        params: Dict[str, Any] = {
            "embed": {"table": embed_init(keys[0], (cfg.vocab_size, cfg.d_model))},
            "final_norm": init_norm(keys[1], cfg.d_model, cfg.norm),
        }
        if not cfg.tie_embeddings:
            params["head"] = {
                "w": dense_init(keys[2], (cfg.d_model, cfg.vocab_size))}

        unit_len = len(self.unit_kinds)
        n_stack = self.n_units * unit_len
        if n_stack:
            bkeys = jax.random.split(keys[3], n_stack).reshape(
                self.n_units, unit_len, 2)
            # vmap twice: over units and positions. Kinds vary by position,
            # so vmap over units only, python-loop positions.
            per_pos = []
            for i, kind in enumerate(self.unit_kinds):
                per_pos.append(jax.vmap(
                    lambda k, kind=kind: self._init_block(k, kind))(bkeys[:, i]))
            # per_pos[i] leaves: (n_units, ...); stack positions on axis 1
            params["blocks"] = jax.tree_util.tree_map(
                lambda *xs: jnp.stack(xs, axis=1), *per_pos)
        if self.tail_kinds:
            tkeys = jax.random.split(keys[4], len(self.tail_kinds) * 2)
            params["tail"] = _tree_stack([
                self._init_block(jax.random.fold_in(keys[4], i), kind)
                for i, kind in enumerate(self.tail_kinds)])
        if cfg.family == "hybrid":
            params["shared"] = self._init_block(keys[5], LayerKind("attn"))
        if cfg.is_encoder_decoder:
            ekeys = jax.random.split(keys[6], cfg.n_encoder_layers)
            params["encoder"] = {
                "blocks": jax.vmap(
                    lambda k: self._init_block(k, LayerKind("attn")))(ekeys),
                "norm": init_norm(keys[7], cfg.d_model, cfg.norm),
            }
        return params

    # ------------------------------------------------------------------
    # block execution (full sequence: train / prefill)
    # ------------------------------------------------------------------

    def _block_full(self, p, h, kind: LayerKind, positions, mode: str,
                    enc_out=None, init_cache=None, length=None):
        """Returns (h, cache_or_None, aux_loss). ``length``: real-token
        count for right-padded prefill buckets (see Model.prefill)."""
        cfg = self.cfg
        # keep the residual stream batch-sharded at every block boundary so
        # GSPMD resolves weight matmuls by gathering weights, not by
        # partial-summing activations across the data axis (§Perf iter 3)
        if cfg.constrain_residual:
            h = logical_constraint(h, ("batch", None, None))
        aux = jnp.zeros((), jnp.float32)
        cache = {}
        if kind.block == "mamba":
            with jax.named_scope("mixer"):
                x = apply_norm(p["ln1"], h, cfg.norm, cfg.norm_eps)
                if mode == "prefill":
                    y, mc = mamba2.mamba_forward(
                        p["mamba"], x, cfg, return_cache=True, length=length)
                    cache["m"] = mc
                else:
                    y = mamba2.mamba_forward(p["mamba"], x, cfg)
                return h + y, cache, aux

        with jax.named_scope("attention"):
            x = apply_norm(p["ln1"], h, cfg.norm, cfg.norm_eps)
            causal = not (cfg.family == "audio" and mode == "encode")
            if cfg.attention == "mla":
                if mode == "prefill":
                    y, ac = attn.mla_forward(p["attn"], x, cfg,
                                             positions=positions,
                                             return_cache=True)
                    cache["a"] = ac
                else:
                    y = attn.mla_forward(p["attn"], x, cfg,
                                         positions=positions)
            else:
                pos = positions if self.use_rope else None
                if mode == "prefill":
                    y, ac = attn.gqa_forward(
                        p["attn"], x, cfg, positions=pos, window=kind.window,
                        causal=causal, return_cache=True, length=length)
                    cache["a"] = ac
                else:
                    y = attn.gqa_forward(p["attn"], x, cfg, positions=pos,
                                         window=kind.window, causal=causal)
            h = h + y
            if kind.cross and enc_out is not None:
                x = apply_norm(p["lnx"], h, cfg.norm, cfg.norm_eps)
                if mode == "prefill":
                    y, xc = attn.gqa_forward(p["xattn"], x, cfg,
                                             cross_x=enc_out,
                                             return_cache=True)
                    cache["x"] = xc
                else:
                    y = attn.gqa_forward(p["xattn"], x, cfg, cross_x=enc_out)
                h = h + y
        with jax.named_scope("moe" if kind.use_moe else "mlp"):
            x = apply_norm(p["ln2"], h, cfg.norm, cfg.norm_eps)
            if kind.use_moe:
                y, aux = moe.moe_forward(p["moe"], x, cfg)
            else:
                y = apply_mlp(p["mlp"], x, cfg.act, self.dtype)
            return h + y, cache, aux

    # ------------------------------------------------------------------
    # block execution (decode: one token)
    # ------------------------------------------------------------------

    def _block_decode(self, p, h, kind: LayerKind, cache, pos, positions,
                      block_tables=None):
        cfg = self.cfg
        if kind.block == "mamba":
            x = apply_norm(p["ln1"], h, cfg.norm, cfg.norm_eps)
            y, mc = mamba2.mamba_decode(p["mamba"], x, cache["m"], cfg)
            return h + y, {"m": mc}
        new_cache = {}
        x = apply_norm(p["ln1"], h, cfg.norm, cfg.norm_eps)
        if "p" in cache:        # paged KV pool (layout owned by repro.dist)
            y, pc = attn.gqa_decode_paged(
                p["attn"], x, cache["p"], pos, block_tables, cfg,
                positions=positions if self.use_rope else None,
                use_rope=self.use_rope)
            new_cache["p"] = pc
            ac = None
        elif cfg.attention == "mla":
            y, ac = attn.mla_decode(p["attn"], x, cache["a"], pos, cfg,
                                    positions=positions)
        else:
            y, ac = attn.gqa_decode(
                p["attn"], x, cache["a"], pos, cfg, window=kind.window,
                positions=positions if self.use_rope else None,
                use_rope=self.use_rope)
        if ac is not None:
            new_cache["a"] = ac
        h = h + y
        if kind.cross and "x" in cache:
            x = apply_norm(p["lnx"], h, cfg.norm, cfg.norm_eps)
            y, _ = attn.gqa_decode(p["xattn"], x, cache["x"], pos, cfg,
                                   cross=True)
            new_cache["x"] = cache["x"]
            h = h + y
        x = apply_norm(p["ln2"], h, cfg.norm, cfg.norm_eps)
        if kind.use_moe:
            y, _ = moe.moe_forward(p["moe"], x, cfg)
        else:
            y = apply_mlp(p["mlp"], x, cfg.act, self.dtype)
        return h + y, new_cache

    def _remat(self, fn):
        if self.cfg.remat_policy == "dots":
            return jax.checkpoint(
                fn, policy=jax.checkpoint_policies
                .dots_with_no_batch_dims_saveable)
        return jax.checkpoint(fn)

    # ------------------------------------------------------------------
    # embedding / encoder front
    # ------------------------------------------------------------------

    def _embed(self, params, tokens, positions, vision_embeds,
               constrain: bool = False):
        cfg = self.cfg
        h = params["embed"]["table"].astype(self.dtype)[tokens]
        # the table is d-over-model sharded (§Perf iter 1); in the
        # inference paths, resolve the lookup result to batch-sharded ONCE
        # here, or every layer's f32 norm internals inherit a model-sharded
        # d and get re-gathered (2 GB f32 gathers per matmul on qwen2-vl
        # prefill — §Perf iter 6). Training is better WITHOUT it (the
        # constraint's transpose inflates the backward by ~50%).
        if constrain:
            h = logical_constraint(h, ("batch", None, None))
        if cfg.family == "vlm" and vision_embeds is not None:
            nv = vision_embeds.shape[1]
            h = jnp.concatenate(
                [vision_embeds.astype(self.dtype), h[:, nv:]], axis=1)
        if cfg.family == "audio":
            pos = jnp.arange(tokens.shape[1]) if positions is None else positions
            h = h + sinusoidal_embedding(pos, cfg.d_model).astype(self.dtype)
        return h

    def _default_positions(self, B, S, offset=0):
        pos = jnp.arange(offset, offset + S)[None, :]
        pos = jnp.broadcast_to(pos, (B, S))
        if self.cfg.mrope_sections:
            pos = jnp.broadcast_to(pos[:, None, :], (B, 3, S))
        return pos

    def _encode(self, params, frames):
        """Whisper encoder on stub frame embeddings (B, enc_seq, d)."""
        cfg = self.cfg
        h = frames.astype(self.dtype)
        h = h + sinusoidal_embedding(
            jnp.arange(h.shape[1]), cfg.d_model).astype(self.dtype)
        kind = LayerKind("attn")

        def body(h, p):
            h, _, _ = self._block_full(p, h, kind, None, "encode")
            return h, None

        h, _ = jax.lax.scan(body, h, params["encoder"]["blocks"])
        return apply_norm(params["encoder"]["norm"], h, cfg.norm, cfg.norm_eps)

    # ------------------------------------------------------------------
    # public API
    # ------------------------------------------------------------------

    def apply(self, params, tokens, *, positions=None, vision_embeds=None,
              frames=None):
        """Full-sequence forward. Returns (logits, aux_loss)."""
        cfg = self.cfg
        B, S = tokens.shape
        if positions is None:
            positions = self._default_positions(B, S)
        enc_out = self._encode(params, frames) if cfg.is_encoder_decoder else None
        with jax.named_scope("embed"):
            h = self._embed(params, tokens, positions, vision_embeds)

        def unit_body(carry, unit_p):
            h, aux = carry
            if cfg.family == "hybrid":
                h, _, _ = self._block_full(params["shared"], h,
                                           LayerKind("attn"), positions,
                                           "train", enc_out)
            for i, kind in enumerate(self.unit_kinds):
                h, _, a = self._block_full(_tree_index(unit_p, i), h, kind,
                                           positions, "train", enc_out)
                aux = aux + a
            return (h, aux), None

        body = self._remat(unit_body) if cfg.remat else unit_body
        aux = jnp.zeros((), jnp.float32)
        with jax.named_scope("layers"):
            if "blocks" in params:
                if cfg.scan_layers:
                    (h, aux), _ = jax.lax.scan(body, (h, aux),
                                               params["blocks"])
                else:
                    for u in range(self.n_units):
                        (h, aux), _ = body(
                            (h, aux), _tree_index(params["blocks"], u))
            for i, kind in enumerate(self.tail_kinds):
                h, _, a = self._block_full(_tree_index(params["tail"], i),
                                           h, kind, positions, "train",
                                           enc_out)
                aux = aux + a
        with jax.named_scope("lm_head"):
            h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
            logits = self._head(params, h)
        return logits, aux

    def _head(self, params, h):
        if self.cfg.tie_embeddings:
            return mdot(h, params["embed"]["table"].T, self.dtype)
        return mdot(h, params["head"]["w"], self.dtype)

    # -------------------------- prefill ------------------------------

    def prefill(self, params, tokens, *, cache_len: Optional[int] = None,
                positions=None, vision_embeds=None, frames=None,
                length=None):
        """Returns (last-token logits (B, vocab), cache).

        ``length``: optional scalar (may be traced) count of REAL tokens
        when ``tokens`` is right-padded to a fixed prefill bucket — the
        compiled serving engine pads prompts to a small set of lengths so
        warmup compiles a fixed program set. With ``length`` set, the
        returned logits are those of token ``length-1``, window caches
        arrange slots by real positions, and SSM states are exactly the
        state after ``length`` tokens (pad dt is zeroed). Full-length KV
        rows past ``length`` hold pad garbage, which decode never attends:
        each step writes position p before attending, and the attention
        mask admits only rows <= p."""
        cfg = self.cfg
        B, S = tokens.shape
        cache_len = cache_len or S
        if positions is None:
            positions = self._default_positions(B, S)
        enc_out = self._encode(params, frames) if cfg.is_encoder_decoder else None
        h = self._embed(params, tokens, positions, vision_embeds,
                        constrain=True)

        def pad_cache(c, kind: LayerKind):
            if kind.block == "mamba" or not c:
                return c
            out = dict(c)
            if "a" in c and "k" in c["a"]:
                L = c["a"]["k"].shape[1]
                tgt = min(cache_len, kind.window) if kind.window > 0 else cache_len
                if L < tgt:
                    out["a"] = {kk: jnp.pad(vv, ((0, 0), (0, tgt - L)) +
                                            ((0, 0),) * (vv.ndim - 2))
                                for kk, vv in c["a"].items()}
            elif "a" in c:  # mla latent cache
                L = c["a"]["c_kv"].shape[1]
                if L < cache_len:
                    out["a"] = {kk: jnp.pad(vv, ((0, 0), (0, cache_len - L), (0, 0)))
                                for kk, vv in c["a"].items()}
            return out

        def unit_body(h, unit_p):
            caches = {}
            if cfg.family == "hybrid":
                h, sc, _ = self._block_full(params["shared"], h,
                                            LayerKind("attn"), positions,
                                            "prefill", enc_out, length=length)
                caches["shared"] = pad_cache(sc, LayerKind("attn"))
            for i, kind in enumerate(self.unit_kinds):
                h, c, _ = self._block_full(_tree_index(unit_p, i), h, kind,
                                           positions, "prefill", enc_out,
                                           length=length)
                caches[str(i)] = pad_cache(c, kind)
            return h, caches

        cache: Dict[str, Any] = {}
        if "blocks" in params:
            if cfg.scan_layers:
                h, unit_caches = jax.lax.scan(unit_body, h, params["blocks"])
            else:
                per_unit = []
                for u in range(self.n_units):
                    h, c = unit_body(h, _tree_index(params["blocks"], u))
                    per_unit.append(c)
                unit_caches = _tree_stack(per_unit)
            cache["units"] = unit_caches
        for i, kind in enumerate(self.tail_kinds):
            h, c, _ = self._block_full(_tree_index(params["tail"], i), h,
                                       kind, positions, "prefill", enc_out,
                                       length=length)
            cache[f"t{i}"] = pad_cache(c, kind)
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        if length is None:
            h_last = h[:, -1:]
        else:
            h_last = jax.lax.dynamic_slice_in_dim(h, length - 1, 1, axis=1)
        logits = self._head(params, h_last)[:, 0]
        return logits, cache

    # -------------------------- decode -------------------------------

    def decode(self, params, cache, token, pos, *, positions=None,
               block_tables=None):
        """One decode step. token: (B,1) int32; pos: scalar absolute
        position, or (B,) per-request positions (continuous batching).
        ``block_tables``: (B, M) int32 per-slot page tables, required when
        the cache holds paged (``p``-layout) KV pools.
        Returns (logits (B, vocab), new_cache)."""
        cfg = self.cfg
        B = token.shape[0]
        if positions is None:
            pa = jnp.asarray(pos)
            p1 = (pa[:, None] if pa.ndim == 1
                  else jnp.broadcast_to(pa[None, None], (B, 1)))
            positions = (jnp.broadcast_to(p1[:, None], (B, 3, 1))
                         if cfg.mrope_sections else p1)
        h = self._embed(params, token, positions, None, constrain=True)

        def unit_body(h, xs):
            unit_p, unit_c = xs
            new_c = {}
            if cfg.family == "hybrid":
                h, sc = self._block_decode(params["shared"], h,
                                           LayerKind("attn"),
                                           unit_c["shared"], pos, positions,
                                           block_tables)
                new_c["shared"] = sc
            for i, kind in enumerate(self.unit_kinds):
                h, c = self._block_decode(_tree_index(unit_p, i), h, kind,
                                          unit_c[str(i)], pos, positions,
                                          block_tables)
                new_c[str(i)] = c
            return h, new_c

        new_cache: Dict[str, Any] = {}
        if "blocks" in params:
            if cfg.scan_layers:
                h, nc = jax.lax.scan(unit_body, h, (params["blocks"],
                                                    cache["units"]))
            else:
                per_unit = []
                for u in range(self.n_units):
                    h, c = unit_body(h, (_tree_index(params["blocks"], u),
                                         _tree_index(cache["units"], u)))
                    per_unit.append(c)
                nc = _tree_stack(per_unit)
            new_cache["units"] = nc
        for i, kind in enumerate(self.tail_kinds):
            h, c = self._block_decode(_tree_index(params["tail"], i), h, kind,
                                      cache[f"t{i}"], pos, positions,
                                      block_tables)
            new_cache[f"t{i}"] = c
        h = apply_norm(params["final_norm"], h, cfg.norm, cfg.norm_eps)
        logits = self._head(params, h)[:, 0]
        return logits, new_cache

    # -------------------------- empty cache --------------------------

    def empty_cache(self, batch: int, cache_len: int, *, page_pool=None):
        """Zero-initialized cache (for dry-run decode lowering).

        ``page_pool``: optional ``(n_pages, page_size)`` — pageable layers
        (see ``pageable``) then hold a global ``p``-layout page pool
        instead of a per-slot ``a`` cache; non-pageable layers keep their
        dense layout, so one cache tree can mix both."""
        cfg = self.cfg
        dt = self.dtype

        def block_cache(kind: LayerKind):
            if kind.block == "mamba":
                return {"m": mamba2.mamba_empty_cache(cfg, batch, dt)}
            c = {}
            if page_pool is not None and self.pageable(kind):
                c["p"] = attn.gqa_empty_page_pool(cfg, *page_pool, dt)
            elif cfg.attention == "mla":
                c["a"] = attn.mla_empty_cache(cfg, batch, cache_len, dt)
            else:
                c["a"] = attn.gqa_empty_cache(cfg, batch, cache_len,
                                              kind.window, dt)
            if kind.cross and cfg.is_encoder_decoder:
                KVH, Dh = cfg.n_kv_heads, cfg.head_dim
                z = jnp.zeros((batch, cfg.encoder_seq, KVH, Dh), dt)
                c["x"] = {"k": z, "v": z}
            return c

        cache: Dict[str, Any] = {}
        if self.n_units:
            unit_c = {str(i): block_cache(k)
                      for i, k in enumerate(self.unit_kinds)}
            if cfg.family == "hybrid":
                unit_c["shared"] = block_cache(LayerKind("attn"))
            cache["units"] = jax.tree_util.tree_map(
                lambda a: jnp.broadcast_to(a[None], (self.n_units,) + a.shape),
                unit_c)
        for i, kind in enumerate(self.tail_kinds):
            cache[f"t{i}"] = block_cache(kind)
        return cache
