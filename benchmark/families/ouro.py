"""Ouro (configs with ``"family": "ouro"``) through
``ray_tpu/models/llama.py``: the LLaMA block (RMSNorm, rotary positions,
full multi-head attention, SwiGLU, untied head) with a second RMSNorm on
each sublayer's output, and the whole stack run ``total_ut_steps`` times
over one set of weights, the final norm after every pass and a KV cache of
its own for every (pass, layer).  The exit gate is not in the program: the
family refuses any ``early_exit_threshold`` but 1, at which it is idle.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("early_exit_threshold", 1), ("sliding_window", None),
                      ("use_sliding_window", False), ("rope_scaling", None),
                      ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    if set(config.get("layer_types", ["full_attention"])) != \
            {"full_attention"}:
        raise ValueError("models/llama.py runs layer_types of "
                         "'full_attention' only")
    if config["head_dim"] * config["num_attention_heads"] != \
            config["hidden_size"]:
        raise ValueError("LlamaConfig derives head_dim as hidden / heads")
    if config["total_ut_steps"] < 1:
        raise ValueError("total_ut_steps must be at least 1")
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
        "ut_steps": config["total_ut_steps"], "post_norm": True,
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg):
    """The tree as the engine stores it (bf16 matrices, f32 norm scales),
    so that the replica's one jitted call never holds the f32 matrices:
    10.7 GB at the published size, beside the 5.3 it keeps."""
    from ray_tpu.models.llama import llama_init, llama_serving_params
    return llama_serving_params(llama_init(rng, cfg), cfg)


def reference_forward(params, tokens, config: dict, gate=None,
                      threshold=None):
    from benchmark.reference import ouro
    return ouro.forward(
        params, tokens, float(config["rope_theta"]), config["rms_norm_eps"],
        config["total_ut_steps"], gate,
        config["early_exit_threshold"] if threshold is None else threshold)


def decode_weight_params(config: dict) -> int:
    """Weights one decode step reads: every layer's projections (Mistral's
    block) once a PASS (5 GB cannot stay in fast memory from one pass to
    the next) and the head once (the embedding is read a row per sequence:
    not counted)."""
    from benchmark.families import mistral
    head = config["hidden_size"] * config["vocab_size"]
    return config["total_ut_steps"] * (
        mistral.decode_weight_params(config) - head) + head


def kv_bytes_per_token(config: dict) -> int:
    """Cached keys and values of one position: every pass's cache of
    every layer, bf16."""
    from benchmark.families import mistral
    return config["total_ut_steps"] * mistral.kv_bytes_per_token(config)
