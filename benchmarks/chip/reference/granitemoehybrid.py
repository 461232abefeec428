"""Plain reference of Granite 4.0-H without experts (Hugging Face
``GraniteMoeHybridForCausalLM``, transformers 4.57
``models/granitemoehybrid/modeling_granitemoehybrid.py``): the embedding
times ``embedding_multiplier``; layers of the kind ``layer_types`` names,

    h  = x + residual_multiplier * Mixer(RMSNorm(x))
    x' = h + residual_multiplier * MLP(RMSNorm(h))

where the mixer is Mamba-2 (``GraniteMoeHybridMambaLayer``) or GQA
self-attention with no position embedding (``position_embedding_type``
"nope") and the softmax scale ``attention_multiplier``
(``GraniteMoeHybridAttention``), and the MLP is
``output_linear(silu(a) * b)``, ``[a, b] = input_linear(u)``
(``GraniteMoeHybridMLP``); a final RMSNorm and the head tied to the
embedding, its logits divided by ``logits_scaling``.

One plain reference per mechanism. The Mamba-2 mixer is
``reference/mamba2.py``'s: its ``causal_conv`` and its recurrence
``scan_ssm``, one time step after the other, joined as its ``mixer`` joins
them, with the gated RMSNorm over the whole inner width, gate before the
norm (``GraniteMoeHybridRMSNormGated``; ``time_step_limit`` is (0, inf),
so dt is not clamped). Causal attention is ``reference/internlm2.py``'s
``causal_attention``, which scales scores by 1/sqrt(head_dim): queries are
multiplied by ``attention_multiplier * sqrt(head_dim)`` first (0.125 at
the published widths, a power of two, so the product is exact). The MLP is
written here: ``reference/internlm2.py`` has it only inside its decoder
layer.

Straightforward ``jax.numpy`` in float32 under
``jax.default_matmul_precision("highest")``. Blockwise only where the
published widths need it to fit one chip beside the state the check keeps
(weights, momentum and the gradient, 3 x 3.8 GB): the recurrence over
blocks of ``HEAD_BLOCK`` heads and attention over the query heads of one
KV head at a time (heads are independent), the loss over blocks of rows,
and one ``lax.scan`` over the Mamba layers, each followed by the attention
layer that comes next in ``layer_types`` (a ``lax.cond``), so each layer's
gradient is written into the stacked gradient in place. Each of these, and
each sublayer, is recomputed in the backward (``jax.checkpoint``), as are
the blocks of the recurrence's time steps and of attention's queries in the
references reused. Weights are read by path from the tree the benchmark
made (the program's layout): ``embed/table`` (V, d); ``blocks/mamba/...``
and ``blocks/attn/...``, each kind's layers stacked (units, layers of that
kind in a unit, ...) in the order of ``layer_types``;
``final_norm/scale``.

Departures from the published model: none in the mathematics.

``mode="fp8"`` is the benchmark's control (``chipbench/refmath.py``).
"""
from __future__ import annotations

import functools
import math
import pathlib

import jax
import jax.numpy as jnp

from chipbench import harness
from chipbench.refmath import mm, rms_norm

_HERE = pathlib.Path(__file__).resolve().parent
_mamba2 = harness.load_module(_HERE / "mamba2.py")
_internlm2 = harness.load_module(_HERE / "internlm2.py")

ROW_BLOCK = 1024     # rows per block of the LM head and loss
HEAD_BLOCK = 16      # heads per block of the mixer's recurrence (the TPU
                     # compiler reckons its backward at 3.5 GB for 64 heads
                     # of 2 x 4096 tokens, 0.9 GB for 16)


def mixer_cfg(cfg: dict) -> dict:
    """The keys ``reference/mamba2.py``'s mixer reads."""
    return {"d_model": cfg["hidden_size"],
            "norm_epsilon": cfg["rms_norm_eps"],
            "ssm_cfg": {"d_state": cfg["mamba_d_state"],
                        "d_conv": cfg["mamba_d_conv"],
                        "expand": cfg["mamba_expand"],
                        "headdim": cfg["mamba_d_head"],
                        "ngroups": cfg["mamba_n_groups"],
                        "chunk_size": cfg["mamba_chunk_size"]}}


def mixer(u, p, cfg, mode):
    """``reference/mamba2.py``'s mixer, with its recurrence
    (``scan_ssm``) run over blocks of ``HEAD_BLOCK`` heads, which are
    independent of each other: in_proj -> [z, xBC, dt]; causal conv and
    SiLU over xBC; dt = softplus(dt + dt_bias); A = -exp(A_log); the
    recurrence; y = RMSNorm(y * silu(z)) over the whole inner width;
    out_proj."""
    m = mixer_cfg(cfg)
    d_in, G, N, P, H, _ = _mamba2.dims(m)
    Bsz, S, _ = u.shape
    proj = mm(u, p["in_proj"], mode)
    z, xbc, dt = (proj[..., :d_in], proj[..., d_in:2 * d_in + 2 * G * N],
                  proj[..., 2 * d_in + 2 * G * N:])
    xbc = jax.nn.silu(_mamba2.causal_conv(xbc, p["conv_w"], p["conv_b"]))
    x = xbc[..., :d_in].reshape(Bsz, S, H, P)
    Bm = xbc[..., d_in:d_in + G * N].reshape(Bsz, S, G, N)
    Cm = xbc[..., d_in + G * N:].reshape(Bsz, S, G, N)
    dt = jax.nn.softplus(dt + p["dt_bias"])
    A = -jnp.exp(p["A_log"])
    hb = min(HEAD_BLOCK, H // G)           # a block's heads share a group

    @jax.checkpoint
    def block(i):
        def heads(a, axis):
            return jax.lax.dynamic_slice_in_dim(a, i * hb, hb, axis)

        def group(a):
            return jax.lax.dynamic_slice_in_dim(a, i * hb // (H // G), 1, 2)

        return _mamba2.scan_ssm(heads(x, 2), heads(dt, 2), heads(A, 0),
                                group(Bm), group(Cm), heads(p["D"], 0))

    y = jax.lax.map(block, jnp.arange(H // hb))     # (H/hb, B, S, hb, P)
    y = jnp.moveaxis(y, 0, 2).reshape(Bsz, S, d_in)
    y = rms_norm(y * jax.nn.silu(z), p["norm_scale"], m["norm_epsilon"])
    return mm(y, p["out_proj"], mode)


def mlp(x, p, mode):
    return mm(jax.nn.silu(mm(x, p["wg"], mode)) * mm(x, p["wi"], mode),
              p["wo"], mode)


def attention(x, p, cfg, mode):
    B, S, d = x.shape
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = d // H
    q = mm(x, p["wq"], mode).reshape(B, S, H, Dh)
    q = q * (cfg["attention_multiplier"] * math.sqrt(Dh))
    k = mm(x, p["wk"], mode).reshape(B, S, KVH, Dh)
    v = mm(x, p["wv"], mode).reshape(B, S, KVH, Dh)
    G = H // KVH

    @jax.checkpoint
    def group(i):            # the G query heads that read KV head i
        return _internlm2.causal_attention(
            jax.lax.dynamic_slice_in_dim(q, i * G, G, 2),
            jax.lax.dynamic_slice_in_dim(k, i, 1, 2),
            jax.lax.dynamic_slice_in_dim(v, i, 1, 2), mode)

    a = jax.lax.map(group, jnp.arange(KVH))         # (KVH, B, S, G, Dh)
    a = jnp.moveaxis(a, 0, 2).reshape(B, S, H * Dh)
    return mm(a, p["wo"], mode)


def layer(h, p, kind, cfg, mode):
    """One layer of ``kind`` ("mamba" or "attention"); each sublayer is
    recomputed in the backward."""
    r, eps = cfg["residual_multiplier"], cfg["rms_norm_eps"]
    if kind == "mamba":
        mix = functools.partial(mixer, cfg=cfg, mode=mode)
        w = p["mamba"]
    else:
        mix = functools.partial(attention, cfg=cfg, mode=mode)
        w = p["attn"]
    h = h + r * jax.checkpoint(mix)(rms_norm(h, p["ln1"]["scale"], eps), w)
    ff = jax.checkpoint(functools.partial(mlp, mode=mode))
    return h + r * ff(rms_norm(h, p["ln2"]["scale"], eps), p["mlp"])


def layers_by_kind(params) -> dict:
    """{kind: weights of that kind's layers, one leading axis, in order}."""
    def flat(tree):
        return jax.tree_util.tree_map(
            lambda a: a.reshape((-1,) + a.shape[2:]), tree)
    blocks = params["blocks"]
    return {"mamba": flat(blocks["mamba"]), "attention": flat(blocks["attn"])}


def attention_after(kinds) -> list:
    """For each Mamba layer in ``kinds``, the index among the attention
    layers of the one that follows it, or -1."""
    after, n_attn = [], 0
    for prev, kind in zip([None] + kinds[:-1], kinds):
        if kind == "mamba":
            after.append(-1)
        else:
            if prev != "mamba":
                raise ValueError("every attention layer follows a Mamba "
                                 f"layer here; got {kinds}")
            after[-1] = n_attn
            n_attn += 1
    return after


def hidden(params, tokens, cfg, mode):
    """Final-normed hidden states (B, S, d): one ``lax.scan`` over the
    Mamba layers, each followed by the attention layer that comes next in
    ``layer_types``, if any. Scanning the stacked weights writes each
    layer's gradient in place, and each step is recomputed in the
    backward, so the backward holds one layer's temporaries."""
    h = params["embed"]["table"][tokens] * cfg["embedding_multiplier"]
    stacks = layers_by_kind(params)
    after = attention_after(cfg["layer_types"][:cfg["num_hidden_layers"]])

    @jax.checkpoint
    def step(h, xs):
        w, a = xs
        h = layer(h, w, "mamba", cfg, mode)

        def attend(h):
            wa = jax.tree_util.tree_map(lambda t: t[a], stacks["attention"])
            return layer(h, wa, "attention", cfg, mode)

        return jax.lax.cond(a >= 0, attend, lambda h: h, h), None

    h, _ = jax.lax.scan(step, h, (stacks["mamba"], jnp.asarray(after)))
    return rms_norm(h, params["final_norm"]["scale"], cfg["rms_norm_eps"])


def logits(params, h, cfg, mode):
    return mm(h, params["embed"]["table"].T, mode) / cfg["logits_scaling"]


def loss(params, tokens, labels, cfg, mode="f32"):
    """Mean next-token cross-entropy over every position."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree_util.tree_map(
            lambda a: a.astype(jnp.float32), params)
        h = hidden(params, tokens, cfg, mode)
        d = h.shape[-1]
        hr, yr = h.reshape(-1, d), labels.reshape(-1)
        rb = min(ROW_BLOCK, hr.shape[0])
        n = hr.shape[0] // rb

        @jax.checkpoint
        def block(i):
            hb = jax.lax.dynamic_slice_in_dim(hr, i * rb, rb)
            yb = jax.lax.dynamic_slice_in_dim(yr, i * rb, rb)
            lg = logits(params, hb, cfg, mode)
            lse = jax.nn.logsumexp(lg, axis=-1)
            return jnp.sum(lse - jnp.take_along_axis(lg, yb[:, None], 1)[:, 0])

        return jnp.sum(jax.lax.map(block, jnp.arange(n))) / hr.shape[0]
