"""Plain reference of InternLM2 (arXiv:2403.17297; Hugging Face
``InternLM2ForCausalLM``): pre-norm decoder layers of RMSNorm -> GQA
self-attention with rotary positions -> residual, RMSNorm -> SiLU-gated MLP
-> residual; a final RMSNorm and an untied LM head.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``: no kernel, no cache, no
batching tricks. It is blockwise only where the published widths need it to
fit one chip: attention over blocks of queries and the loss over blocks of
rows, each recomputed in the backward (``jax.checkpoint``), which changes
the order of no sum that matters.

Weights are read by path from the tree the benchmark made (the program's
layout): ``embed/table`` (V, d); ``blocks/...`` stacked over layers with a
unit axis of 1; ``final_norm/scale``; ``head/w`` (d, V). Query head h reads
KV head h // (H / KVH), the grouping of InternLM2's packed ``wqkv``.

Departures from the published model: none in the mathematics. Rotary
scaling is not applied; the published ``rope_scaling`` engages only past
``max_position_embeddings`` (32768), beyond every length used here.

``mode="fp8"`` is the benchmark's control (``chipbench/refmath.py``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench.refmath import mm, rms_norm

Q_BLOCK = 512        # queries per attention block
ROW_BLOCK = 1024     # rows per block of the LM head and loss


# ------------------------------------------------------------ layers


def rope(x, positions, theta):
    """Rotate-half rotary embedding. x: (B, S, H, Dh); positions: (S,)."""
    Dh = x.shape[-1]
    inv = theta ** (-jnp.arange(0, Dh // 2, dtype=jnp.float32) / (Dh // 2))
    ang = positions[:, None].astype(jnp.float32) * inv      # (S, Dh/2)
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)[None, :, None, :]
    x1, x2 = x[..., :Dh // 2], x[..., Dh // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def causal_attention(q, k, v, mode):
    """q: (B, S, H, Dh); k, v: (B, S, KVH, Dh). Softmax over keys j <= i,
    one block of Q_BLOCK queries at a time."""
    B, S, H, Dh = q.shape
    KVH = k.shape[2]
    G = H // KVH
    kh = jnp.repeat(k, G, axis=2).transpose(0, 2, 3, 1)     # (B, H, Dh, S)
    vh = jnp.repeat(v, G, axis=2).transpose(0, 2, 1, 3)     # (B, H, S, Dh)
    qb = min(Q_BLOCK, S)
    nb = S // qb

    @jax.checkpoint
    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qb, qb, axis=1)
        s = mm(qi.transpose(0, 2, 1, 3), kh, mode) / jnp.sqrt(
            jnp.float32(Dh))                                 # (B, H, qb, S)
        qpos = i * qb + jnp.arange(qb)
        s = jnp.where(jnp.arange(S)[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return mm(p, vh, mode)                              # (B, H, qb, Dh)

    out = jax.lax.map(block, jnp.arange(nb))            # (nb, B, H, qb, Dh)
    return out.transpose(1, 0, 3, 2, 4).reshape(B, S, H, Dh)


def layer(h, p, cfg, mode):
    """One decoder layer. ``p`` holds one layer's weights (unit axis
    dropped)."""
    B, S, d = h.shape
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = cfg.get("head_dim", d // H)
    eps, theta = cfg["rms_norm_eps"], cfg["rope_theta"]
    pos = jnp.arange(S)
    x = rms_norm(h, p["ln1"]["scale"], eps)
    q = mm(x, p["attn"]["wq"], mode).reshape(B, S, H, Dh)
    k = mm(x, p["attn"]["wk"], mode).reshape(B, S, KVH, Dh)
    v = mm(x, p["attn"]["wv"], mode).reshape(B, S, KVH, Dh)
    q, k = rope(q, pos, theta), rope(k, pos, theta)
    a = causal_attention(q, k, v, mode).reshape(B, S, H * Dh)
    h = h + mm(a, p["attn"]["wo"], mode)
    x = rms_norm(h, p["ln2"]["scale"], eps)
    gate = jax.nn.silu(mm(x, p["mlp"]["wg"], mode))
    up = mm(x, p["mlp"]["wi"], mode)
    return h + mm(gate * up, p["mlp"]["wo"], mode)


def _f32(tree):
    return jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), tree)


def hidden(params, tokens, cfg, mode):
    """Final-normed hidden states (B, S, d), in float32 (weights are taken
    to float32 one layer at a time)."""
    h = params["embed"]["table"][tokens].astype(jnp.float32)
    blocks = jax.tree_util.tree_map(lambda a: a[:, 0], params["blocks"])

    def body(h, p):
        return jax.checkpoint(functools.partial(layer, cfg=cfg, mode=mode))(
            h, _f32(p)), None

    h, _ = jax.lax.scan(body, h, blocks)
    return rms_norm(h, params["final_norm"]["scale"].astype(jnp.float32),
                    cfg["rms_norm_eps"])


def logits(params, h, mode):
    return mm(h, params["head"]["w"].astype(jnp.float32), mode)


def loss(params, tokens, labels, cfg, mode="f32"):
    """Mean next-token cross-entropy over every position."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, cfg, mode)
        d = h.shape[-1]
        hr, yr = h.reshape(-1, d), labels.reshape(-1)
        rb = min(ROW_BLOCK, hr.shape[0])
        n = hr.shape[0] // rb

        @jax.checkpoint
        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(hr, i * rb, rb)
            yb = jax.lax.dynamic_slice_in_dim(yr, i * rb, rb)
            lg = logits(params, hb, mode)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], 1)[:, 0])

        return jnp.sum(jax.lax.map(block, jnp.arange(n))) / hr.shape[0]


def next_token_logits(params, tokens, targets, cfg, mode="f32"):
    """The full forward over ``tokens`` (R, T). For every position: the
    largest logit (R, T), the logits of the candidate next tokens
    ``targets`` (R, T, K), and the token with the largest logit (R, T).
    Computed over blocks of rows, so the (R, T, vocab) logits never exist
    at once."""
    with jax.default_matmul_precision("highest"):
        h = hidden(params, tokens, cfg, mode)
        R, T, d = h.shape
        hr, tr = h.reshape(-1, d), targets.reshape(R * T, -1)
        rb = min(ROW_BLOCK, hr.shape[0])

        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(hr, i * rb, rb)
            tb = jax.lax.dynamic_slice_in_dim(tr, i * rb, rb)
            lg = logits(params, hb, mode)
            return (jnp.max(lg, -1), jnp.take_along_axis(lg, tb, 1),
                    jnp.argmax(lg, -1).astype(jnp.int32))

        top, picked, best = jax.lax.map(block, jnp.arange(hr.shape[0] // rb))
        return (top.reshape(R, T), picked.reshape(R, T, -1),
                best.reshape(R, T))
