"""granite-4.0-h-micro [hybrid] — Mamba-2 and NoPE GQA attention in a
period of 10 layers (attention at position 5), an MLP after every mixer of
either kind, and muP-style multipliers on the embedding, the residual
branches and the logits.
[hf:ibm-granite/granite-4.0-h-micro]"""
from repro.configs.base import ModelConfig, SSMConfig

ARCH_ID = "granite-4.0-h-micro"

# one period of the published layer_types: 9 Mamba-2 layers, 1 attention
PATTERN = ("mamba",) * 5 + ("attention",) + ("mamba",) * 4


def config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID, family="hybrid",
        n_layers=40, d_model=2048, n_heads=32, n_kv_heads=8, head_dim=64,
        d_ff=8192, vocab_size=100352,
        attention="gqa", qkv_bias=False, rope_theta=10_000.0,
        position_embedding="nope", attention_scale=0.015625,
        ssm=SSMConfig(d_state=128, d_conv=4, expand=2, head_dim=64,
                      n_groups=1, chunk_size=256),
        layer_pattern=PATTERN,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0,
        norm="rmsnorm", norm_eps=1e-5, tie_embeddings=True, act="silu",
    )


def smoke_config() -> ModelConfig:
    return ModelConfig(
        name=ARCH_ID + "-smoke", family="hybrid",
        n_layers=10, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
        d_ff=128, vocab_size=256,
        attention="gqa", position_embedding="nope", attention_scale=0.0625,
        ssm=SSMConfig(d_state=16, d_conv=4, expand=2, head_dim=16,
                      n_groups=1, chunk_size=16),
        layer_pattern=PATTERN,
        embedding_multiplier=12.0, residual_multiplier=0.22,
        logits_scaling=8.0,
        norm="rmsnorm", norm_eps=1e-5, tie_embeddings=True, act="silu",
        dtype="float32", remat=False,
    )
