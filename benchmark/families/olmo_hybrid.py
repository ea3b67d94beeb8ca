"""Olmo-Hybrid (configs with ``"family": "olmo_hybrid"``) through
``ray_tpu/models/llama.py``: a stack of two kinds of layer in a repeating
pattern, three linear-attention layers (the gated delta rule:
``ray_tpu/ops/linear_attention.py``) to one of full attention, OLMo 2's
block around both (a sublayer's OUTPUT RMS-normed and nothing on its input,
q and k RMS-normed over the whole projection), nothing rotated, SwiGLU,
untied head.  Served, a linear layer keeps a float32 state row and a
convolution tail a decode SLOT beside the full layers' K/V pages.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"
KINDS = {"linear_attention": "linear", "full_attention": "full"}


def pattern(config: dict) -> tuple:
    """One period of ``layer_types`` in the program's names; the list has
    to be whole periods of it."""
    kinds = config["layer_types"]
    if set(kinds) - set(KINDS):
        raise ValueError(f"models/llama.py runs layer_types of {set(KINDS)} "
                         f"only, not {set(kinds) - set(KINDS)}")
    period = kinds.index("full_attention") + 1 \
        if "full_attention" in kinds else len(kinds)
    if len(kinds) != config["num_hidden_layers"] or len(kinds) % period \
            or kinds != kinds[:period] * (len(kinds) // period):
        raise ValueError("layer_types has to be num_hidden_layers long and "
                         "whole periods of one pattern")
    return tuple(KINDS[kind] for kind in kinds[:period])


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False),
                      ("rope_parameters", {"rope_theta": None})):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("LlamaConfig derives head_dim as hidden / heads")
    if config["linear_num_key_heads"] != config["linear_num_value_heads"]:
        raise ValueError("models/llama.py runs linear layers with a key "
                         "head for every value head")
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["intermediate_size"],
        "rms_eps": config["rms_norm_eps"],
        "rope_theta": 0.0, "qk_norm": True,
        "pre_norm": False, "post_norm": True,
        "layer_pattern": pattern(config),
        "linear_heads": config["linear_num_value_heads"],
        "linear_key_dim": config["linear_key_head_dim"],
        "linear_value_dim": config["linear_value_head_dim"],
        "linear_conv": config["linear_conv_kernel_dim"],
        "linear_neg_eigval": config["linear_allow_neg_eigval"],
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg):
    """The tree as the engine stores it (bf16 matrices; f32 norm scales,
    ``A_log`` and ``dt_bias``), so that the replica's one jitted call never
    holds the f32 matrices: 13 GB at the cell's size beside the 6.5 it
    keeps."""
    from ray_tpu.models.llama import llama_init, llama_serving_params
    return llama_serving_params(llama_init(rng, cfg), cfg)


def reference_forward(params, tokens, config: dict):
    from benchmark.reference import olmo_hybrid
    return olmo_hybrid.forward(params, tokens, config)


def layer_counts(config: dict) -> dict:
    kinds = config["layer_types"]
    return {"linear": kinds.count("linear_attention"),
            "full": kinds.count("full_attention")}


def linear_shape(config: dict) -> dict:
    """What ``costs_linear`` needs of the linear layers."""
    return {"layers": layer_counts(config)["linear"],
            "heads": config["linear_num_value_heads"],
            "key_dim": config["linear_key_head_dim"],
            "value_dim": config["linear_value_head_dim"]}


def layer_params(config: dict) -> dict:
    """Parameters of one layer of either kind (matrices and the rest)."""
    D, M = config["hidden_size"], config["intermediate_size"]
    N, dk, dv = (config["linear_num_value_heads"],
                 config["linear_key_head_dim"],
                 config["linear_value_head_dim"])
    C = N * (2 * dk + dv)
    swiglu, norms = 3 * D * M, 2 * D
    return {"linear": D * (C + N * dv + 2 * N)
            + config["linear_conv_kernel_dim"] * C + 2 * N + dv
            + N * dv * D + swiglu + norms,
            "full": 4 * D * D + 2 * D + swiglu + norms}


def decode_weight_params(config: dict) -> int:
    """Weights one decode step reads: every layer of both kinds and the
    head once (the embedding is read a row per sequence: not counted)."""
    counts, each = layer_counts(config), layer_params(config)
    return sum(counts[kind] * each[kind] for kind in counts) \
        + config["hidden_size"] * (config["vocab_size"] + 1)


def kv_bytes_per_token(config: dict) -> int:
    """Cached keys and values of one position: the FULL layers' alone,
    bf16."""
    return layer_counts(config)["full"] * 2 * config["hidden_size"] * 2


def state_bytes_per_slot(config: dict) -> int:
    """What a decode slot keeps for the linear layers: a float32 state
    [heads, key_dim, value_dim] a layer (the convolution's tail, 1.5% of
    it, is not counted)."""
    shape = linear_shape(config)
    return shape["layers"] * shape["heads"] * shape["key_dim"] \
        * shape["value_dim"] * 4
