"""Granite 4.0-H's work per token and per kernel call, from the published
keys (``GraniteMoeHybridForCausalLM`` without experts).

A layer is a Mamba-2 mixer or GQA attention, each followed by the gated MLP
(``shared_intermediate_size`` wide); ``layer_types`` says which, for the
first ``num_hidden_layers`` layers. The mixer is counted as
``models/mamba2.py`` counts it, attention and the MLP as
``models/internlm2.py`` counts a decoder layer (the position embedding is
not counted there, so NoPE changes nothing), and the tied LM head as
2 d V. Forward plus backward is three times the forward.
"""
from __future__ import annotations

import pathlib

from chipbench import harness

_HERE = pathlib.Path(__file__).resolve().parent
_mamba2 = harness.load_module(_HERE / "mamba2.py")
_internlm2 = harness.load_module(_HERE / "internlm2.py")


def layer_counts(cfg: dict) -> dict:
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    return {"mamba": kinds.count("mamba"),
            "attention": kinds.count("attention")}


def mixer_cfg(cfg: dict, layers: int) -> dict:
    """``layers`` Mamba-2 mixers and no head, in ``models/mamba2.py``'s
    keys."""
    return {"d_model": cfg["hidden_size"], "n_layer": layers,
            "vocab_size": 0,
            "ssm_cfg": {"d_state": cfg["mamba_d_state"],
                        "d_conv": cfg["mamba_d_conv"],
                        "expand": cfg["mamba_expand"],
                        "headdim": cfg["mamba_d_head"],
                        "ngroups": cfg["mamba_n_groups"],
                        "chunk_size": cfg["mamba_chunk_size"]}}


def decoder_cfg(cfg: dict, layers: int, d_ff: int) -> dict:
    """``layers`` decoder layers of attention (``d_ff`` 0: none of the MLP)
    and the MLP, and no head, in ``models/internlm2.py``'s keys."""
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"hidden_size": d, "num_attention_heads": H,
            "num_key_value_heads": cfg["num_key_value_heads"],
            "head_dim": d // H, "intermediate_size": d_ff, "vocab_size": 0,
            "num_hidden_layers": layers}


def fwd_flops_per_token(cfg: dict, seq: int) -> float:
    n = layer_counts(cfg)
    F = cfg["shared_intermediate_size"]
    mlp = 2 * 3 * cfg["hidden_size"] * F
    return (_mamba2.fwd_flops_per_token(mixer_cfg(cfg, n["mamba"]), seq)
            + n["mamba"] * mlp
            + _internlm2.fwd_flops_per_token(
                decoder_cfg(cfg, n["attention"], F), seq)
            + 2 * cfg["hidden_size"] * cfg["vocab_size"])


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3 * fwd_flops_per_token(cfg, seq)


def train_kernels(cfg: dict, batch: int, seq: int) -> dict:
    """{kernel: (ops, bytes)} of one training step: the SSD kernels of the
    Mamba layers and the flash-attention kernels of the attention layers."""
    n = layer_counts(cfg)
    out = {}
    if n["mamba"]:
        out.update(_mamba2.train_kernels(mixer_cfg(cfg, n["mamba"]), batch,
                                         seq))
    if n["attention"]:
        out.update(_internlm2.train_kernels(
            decoder_cfg(cfg, n["attention"], 0), batch, seq))
    return out


def param_count(cfg: dict) -> int:
    """Parameters of the configuration's layers, final norm and tied
    embedding, from the published keys."""
    n = layer_counts(cfg)
    d, F = cfg["hidden_size"], cfg["shared_intermediate_size"]
    H, KVH = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    Dh = d // H
    d_in = cfg["mamba_expand"] * d
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    nh = cfg["mamba_n_heads"]
    conv_dim = d_in + 2 * gn
    norms_mlp = 2 * d + 3 * d * F
    mixer = (d * (2 * d_in + 2 * gn + nh) + cfg["mamba_d_conv"] * conv_dim
             + conv_dim * cfg["mamba_conv_bias"] + 3 * nh + d_in + d_in * d)
    attn = d * H * Dh + 2 * d * KVH * Dh + H * Dh * d
    return (n["mamba"] * (mixer + norms_mlp) + n["attention"] * (attn
            + norms_mlp) + cfg["vocab_size"] * d + d)
