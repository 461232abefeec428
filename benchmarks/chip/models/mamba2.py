"""Mamba-2's work per token and per kernel call, from the published keys.

Forward per token: the in/out projections, the depthwise causal conv, the
chunked SSD and the tied LM head. The SSD counts intra-chunk scores
C_i.B_j over the causal half of the chunk (per group), their weighted sum
of dt*x (per head), the chunk state and the state's contribution to the
output; the recurrence over chunk states is O(1/chunk) per token and not
counted. Forward plus backward is three times the forward.
"""
from __future__ import annotations

from chipbench import flops


def padded_vocab(cfg: dict) -> int:
    """The embedding's rows: the vocabulary rounded up to
    ``pad_vocab_size_multiple``."""
    v, m = cfg["vocab_size"], cfg.get("pad_vocab_size_multiple", 1)
    return -(-v // m) * m


def dims(cfg: dict) -> dict:
    s = cfg["ssm_cfg"]
    d = cfg["d_model"]
    d_in = s["expand"] * d
    P, N, G = s["headdim"], s["d_state"], s["ngroups"]
    return {"d": d, "d_in": d_in, "P": P, "N": N, "G": G, "H": d_in // P,
            "K": s["d_conv"], "chunk": s["chunk_size"],
            "conv_dim": d_in + 2 * G * N, "V": padded_vocab(cfg),
            "L": cfg["n_layer"]}


def fwd_flops_per_token(cfg: dict, seq: int) -> float:
    m = dims(cfg)
    d, d_in, G, N, H, P = m["d"], m["d_in"], m["G"], m["N"], m["H"], m["P"]
    proj = 2 * d * (2 * d_in + 2 * G * N + H) + 2 * d_in * d
    conv = 2 * m["K"] * m["conv_dim"]
    per_pos = flops.causal_pairs(m["chunk"]) / m["chunk"]
    ssd = 2 * G * N * per_pos + 2 * H * P * per_pos + 4 * H * P * N
    return m["L"] * (proj + conv + ssd) + 2 * d * m["V"]


def train_flops_per_token(cfg: dict, seq: int) -> float:
    return 3 * fwd_flops_per_token(cfg, seq)


def train_kernels(cfg: dict, batch: int, seq: int) -> dict:
    """{kernel: (ops, bytes)} of one training step: the SSD intra-chunk
    kernels see x, B and C in bfloat16 (the compute dtype)."""
    m = dims(cfg)
    ops, nbytes = flops.ssd_train(batch, seq, m["H"], m["P"], m["G"], m["N"],
                                  m["chunk"], x_itemsize=2, bc_itemsize=2)
    return {"ssd": (m["L"] * ops, m["L"] * nbytes)}
