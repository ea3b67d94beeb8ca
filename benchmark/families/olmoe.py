"""OLMoE (configs with ``"family": "olmoe"``) through
``ray_tpu/models/llama.py``: RMSNorm, q/k RMSNorm over the whole projection,
rotary positions, full multi-head attention, 64 SwiGLU experts routed top-8
per token without capacity (``ray_tpu/ops/moe.py``'s dropless path), untied
head.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("clip_qkv", None), ("attention_bias", False),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False)):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("LlamaConfig derives head_dim as hidden / heads")
    if not 0 < config["num_experts_per_tok"] <= config["num_experts"]:
        raise ValueError("num_experts_per_tok must be in 1..num_experts")
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "qk_norm": True, "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg):
    from ray_tpu.models.llama import llama_init
    return llama_init(rng, cfg)


def reference_forward(params, tokens, config: dict, with_gates=False):
    from benchmark.reference import olmoe
    return olmoe.forward(params, tokens, float(config["rope_theta"]),
                         config["rms_norm_eps"],
                         config["num_experts_per_tok"],
                         config["norm_topk_prob"], with_gates)
