"""Mistral (configs with ``"family": "mistral"``) through
``ray_tpu/models/llama.py``: RMSNorm, rotary positions, grouped-query
attention, SwiGLU, untied head.  No sliding window (v0.3 has none).
"""

from __future__ import annotations

ENGINE_MODEL = "llama"


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    if config.get("sliding_window") is not None:
        raise ValueError("models/llama.py has no sliding window")
    if config["head_dim"] * config["num_attention_heads"] != \
            config["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden / heads")
    return LlamaConfig(vocab_size=config["vocab_size"],
                       num_layers=config["num_hidden_layers"],
                       num_heads=config["num_attention_heads"],
                       num_kv_heads=config["num_key_value_heads"],
                       embed_dim=config["hidden_size"],
                       mlp_dim=config["intermediate_size"],
                       rope_theta=config["rope_theta"],
                       rms_eps=config["rms_norm_eps"],
                       max_seq_len=max_seq_len, **overrides)


def init(rng, cfg):
    from ray_tpu.models.llama import llama_init
    return llama_init(rng, cfg)


def reference_forward(params, tokens, config: dict):
    from benchmark.reference import mistral
    return mistral.forward(params, tokens, config["rope_theta"],
                           config["rms_norm_eps"])


def decode_weight_params(config: dict) -> int:
    """Weights one decode step reads whole: every layer's projections and
    the head (the embedding is read a row per sequence: not counted)."""
    d, m = config["hidden_size"], config["intermediate_size"]
    h = config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    layer = d * heads * h * 2 + d * 2 * kv * h + 3 * d * m
    return config["num_hidden_layers"] * layer + d * config["vocab_size"]


def kv_bytes_per_token(config: dict) -> int:
    """Cached keys and values of one position, all layers, bf16."""
    return (config["num_hidden_layers"] * 2 * config["num_key_value_heads"]
            * config["head_dim"] * 2)
