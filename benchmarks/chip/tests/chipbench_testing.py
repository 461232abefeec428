"""Small cells for the CPU tests: the same jobs, references and checks as
the chip cells, at widths a test run can hold."""
from __future__ import annotations

import pathlib
import sys
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parents[1] / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

from chipbench import harness  # noqa: E402

INTERNLM2_SMALL = {
    "name": "internlm2-small", "model_type": "internlm2",
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "num_hidden_layers": 2,
    "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 1000000,
    "tie_word_embeddings": False, "bias": False, "hidden_act": "silu",
    "program": {
        "registry": "internlm2-1.8b",
        "set": {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                "head_dim": 16, "d_ff": 128, "vocab_size": 256,
                "norm_eps": 1e-5, "attention_chunk": 16},
        "check": {"d_model": "hidden_size", "n_heads": "num_attention_heads",
                  "n_kv_heads": "num_key_value_heads", "d_ff":
                  "intermediate_size", "vocab_size": "vocab_size",
                  "n_layers": "num_hidden_layers", "norm_eps": "rms_norm_eps"},
    },
}

TRAIN_SMALL = {"job": "train_phase1", "seq": 32, "batch": 4, "rows": 32,
               "markov_states": 64, "corpus_seed": 7, "lr": 0.02,
               "lr_decay_steps": 2000,
               "momentum": 0.9, "weight_decay": 0.0005}

# Limits of the training comparison at this size, set by the cells' rule
# from readings on the CPU over 12 seeds: the sound program read at most
# loss 0.00101, first gradient 0.00449, change 0.00368; the float8 control
# at least 0.00287, 0.01799, 0.01638.
TRAIN_SMALL_LIMITS = {"loss_gap": 0.002, "first_grad_gap": 0.009,
                      "delta_gap": 0.009}


def cell(config: dict, traffic: dict, limits: dict, chips: int = 1,
         name: str = "small") -> harness.Cell:
    return harness.Cell(name=name, chips=chips, config=config,
                        traffic=traffic, limits=limits, end_to_end=[],
                        per_layer=[], bench=BENCH)


def run_job(c: harness.Cell, seed: int = 12345678901, seconds: float = 0.3):
    import jax
    run = harness.Run(c, seed, seconds, False, jax.devices()[:c.chips],
                      time.perf_counter())
    return c.job().run(run)


MAMBA2_SMALL = {
    "name": "mamba2-small", "model_type": "mamba2",
    "d_model": 64, "n_layer": 2, "vocab_size": 250,
    "pad_vocab_size_multiple": 16, "tie_embeddings": True,
    "norm_epsilon": 1e-5,
    "ssm_cfg": {"d_state": 16, "d_conv": 4, "expand": 2, "headdim": 16,
                "ngroups": 1, "chunk_size": 16},
    "program": {
        "registry": "mamba2-2.7b",
        "set": {"n_layers": 2, "d_model": 64, "vocab_size": 256,
                "norm_eps": 1e-5, "tie_embeddings": True,
                "head_dim": 16},
        "ssm": {"d_state": 16, "d_conv": 4, "expand": 2, "head_dim": 16,
                "n_groups": 1, "chunk_size": 16},
        "check": {"d_model": "d_model", "n_layers": "n_layer",
                  "vocab_size": 256, "tie_embeddings": "tie_embeddings",
                  "norm_eps": "norm_epsilon",
                  "ssm.d_state": "ssm_cfg.d_state",
                  "ssm.head_dim": "ssm_cfg.headdim",
                  "ssm.chunk_size": "ssm_cfg.chunk_size"},
    },
}

SERVE_SMALL = {
    "job": "serve_open_loop", "rate": 12.0, "shape_seed": 11,
    "prompt": {"median": 20, "sigma": 1.0, "min": 8, "max": 60},
    "output": {"median": 8, "sigma": 0.7, "min": 2, "max": 30},
    "max_total": 120, "markov_states": 64, "drain_seconds": 60,
    "trace_seconds": 1.0, "check_requests": 4, "check_tokens": 30,
    "engine": {"max_batch": 4, "max_seq": 128, "decode_block": 4,
               "page_size": 16, "kv_cache_dtype": "int8",
               "buckets": [16, 32, 64, 128]},
}

# The serving comparison's limit at this size, from readings on the CPU over
# 12 seeds: the sound program's widest gap at most 0.0218, the float8
# control's at least 0.137.
SERVE_SMALL_LIMITS = {"served_logit_gap": 0.06}
