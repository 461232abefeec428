"""InternLM2's work per token and per kernel call, from the published keys.

Forward per token: the layer matmuls, causal attention (QK^T and PV over
the causal half) and the untied LM head. Norms, RoPE and the softmax are
not counted. Forward plus backward is three times the forward.
"""
from __future__ import annotations

from chipbench import flops


def dims(cfg: dict) -> dict:
    d, H = cfg["hidden_size"], cfg["num_attention_heads"]
    return {"d": d, "H": H, "KVH": cfg["num_key_value_heads"],
            "Dh": cfg.get("head_dim", d // H), "F": cfg["intermediate_size"],
            "V": cfg["vocab_size"], "L": cfg["num_hidden_layers"]}


def fwd_flops_per_token(cfg: dict, seq: int) -> float:
    m = dims(cfg)
    d, H, KVH, Dh, F = m["d"], m["H"], m["KVH"], m["Dh"], m["F"]
    proj = d * H * Dh + 2 * d * KVH * Dh + H * Dh * d + 3 * d * F
    attn = 2 * 2 * H * Dh * flops.causal_pairs(seq) / seq
    return m["L"] * (2 * proj + attn) + 2 * d * m["V"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3 * fwd_flops_per_token(cfg, seq)


def train_kernels(cfg: dict, batch: int, seq: int) -> dict:
    """{kernel: (ops, bytes)} of one training step."""
    m = dims(cfg)
    ops, nbytes = flops.flash_attention_train(batch, seq, m["H"], m["KVH"],
                                              m["Dh"])
    return {"flash_attention": (m["L"] * ops, m["L"] * nbytes)}
