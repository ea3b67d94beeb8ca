"""LFM2's mixture-of-experts line (configs with ``"family": "lfm2_moe"``;
LFM2-24B-A2B) through ``ray_tpu/models/llama.py``: a stack of two kinds of
layer, gated short convolutions (``_conv_operator``: ``[B | C | z] = x W_in``,
a causal depthwise convolution of ``B * z`` over ``conv_L_cache`` positions
with no activation, ``C *`` and out) three to one of grouped-query attention
with heads of 64, q and k normed each head by itself and rotated; leading
dense layers ahead of expert layers that route top-4 of 64 by sigmoid scores
with a selection bias, gates renormalised over their sum + 1e-6, no shared
expert (``ray_tpu/ops/moe.py``'s dropless path); the head is the table.
Served, a conv layer keeps of its past a tail of ``conv_L_cache - 1``
positions a decode SLOT and no state matrix, beside the attention layers'
K/V pages.
"""

from __future__ import annotations

from benchmark.families.xing import CODE_SPARE, CODE_WEIGHT

ENGINE_MODEL = "llama"
ROUTER_EPS = 1e-6
# The span of the routing bias (``_with_routing_code``): Kimi's, and for its
# reason: a program that let the bias into the GATES moves them by up to a
# fifth, which the check then sees (at Xing's 0.01 it would not), and the
# code's experts still score over 0.9 where the others score 0.5.
BIAS_SPAN = 0.2
KINDS = {"conv": "conv", "full_attention": "full"}


def pattern(config: dict) -> tuple:
    """The stack's kinds in the program's names, as one period (the program
    takes any whole number of periods; the cut's nine layers are one)."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types names every layer once, 'conv' or "
                         "'full_attention'")
    return tuple(KINDS[kind] for kind in kinds)


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("conv_bias", False), ("use_expert_bias", True),
                      ("tie_word_embeddings", True), ("hidden_act", "silu")):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    rope = config["rope_parameters"]
    if rope.get("rope_type", "default") != "default":
        raise ValueError("models/llama.py rotates by the default tables, "
                         f"not {rope['rope_type']!r}")
    if not 0 < config["num_dense_layers"] < config["num_hidden_layers"]:
        raise ValueError("num_dense_layers must leave at least one dense "
                         "and one expert layer")
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "head_size": config["assumed_sizes"]["head_dim"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["moe_intermediate_size"],
        "rope_theta": float(rope["rope_theta"]),
        "rms_eps": config["norm_eps"],
        "qk_norm_per_head": True,
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "router_scoring": "sigmoid", "router_bias": True,
        "router_norm_eps": ROUTER_EPS,
        "routed_scaling": float(config["routed_scaling_factor"]),
        "first_dense_layers": config["num_dense_layers"],
        "dense_mlp_dim": config["intermediate_size"],
        "layer_pattern": pattern(config),
        "linear_conv": config["conv_L_cache"],
        "tie_embeddings": True,
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg):
    """The tree as the engine stores it, so that the replica's one jitted
    call never holds the f32 matrices (21 GB at the published size beside
    the 10.4 it keeps): bf16 matrices, the routed experts and the
    convolution's taps among them; f32 norm scales, router and routing
    bias.  The routing is drawn as a code with wide margins
    (``_with_routing_code``; ``families/xing.py`` says why: the 4th and 5th
    of 64 scores of a random router lie within bf16's rounding of its
    input, and a swapped expert reads as a fault)."""
    from ray_tpu.models.gpt import _cast_leaves
    from ray_tpu.models.llama import llama_init, llama_serving_params
    stored = llama_serving_params(llama_init(rng, cfg), cfg)
    layers = tuple(
        {**group, "mlp": _cast_leaves(group["mlp"], cfg.dtype, "wgu", "wd")}
        for group in stored["layers"])
    return _with_routing_code({**stored, "layers": layers}, rng, cfg)


def _with_routing_code(params, rng, cfg):
    """``families/kimi_linear.py::_with_routing_code`` for this tree (a group
    a layer, each a stack of one; a plain residual stream; the operator's
    way out is ``conv.wout`` or ``attn.wo``): the first E = ``num_experts``
    places of the stream carry a token's code (one value at
    ``experts_per_token + CODE_SPARE`` of the E places, 0 at the others), no
    sublayer writes there, so every layer's normed input holds one common
    value at the code's places; an expert layer's router reads only those
    places, each expert its own through a seeded permutation, with weight
    ``CODE_WEIGHT``, and the bias, E evenly spaced values over
    +-``BIAS_SPAN`` in a seeded order, chooses which ``experts_per_token`` of
    the code's experts run: the same experts in bfloat16 as in float32.  The
    head is the table, so the code's places take part in the logits (a token
    whose code shares places with the last position's scores a little
    higher): the same arithmetic on both sides of the check."""
    import jax
    import jax.numpy as jnp
    E, hot = cfg.num_experts, cfg.experts_per_token + CODE_SPARE
    V, D = params["wte"].shape
    if E & (E - 1) or hot > E or E > D:
        raise ValueError("the routing code is written for a power of two "
                         f"of experts within the stream's width, not {E}")
    keep = (jnp.arange(D) >= E)                   # the stream's other places
    k = jax.random.split(jax.random.fold_in(rng, 0x726F7574), 4)    # "rout"
    start = jax.random.randint(k[0], (V,), 0, E)
    step = 2 * jax.random.randint(k[1], (V,), 0, E // 2) + 1
    named = (start[:, None] + step[:, None] * jnp.arange(hot)) % E  # [V, hot]
    code = (named[:, :, None] == jnp.arange(E)).any(axis=1)         # [V, E]
    wte = params["wte"].at[:, :E].set(
        (0.02 * (D / E) ** 0.5 * code).astype(params["wte"].dtype))

    def coded(at, group):
        mixer, out_name = ("conv", "wout") if "conv" in group else \
            ("attn", "wo")
        out = {**group,
               mixer: {**group[mixer],
                       out_name: group[mixer][out_name] * keep},
               "mlp": {**group["mlp"], "wd": group["mlp"]["wd"] * keep}}
        if "router" not in group["mlp"]:
            return out
        reads = jax.nn.one_hot(jax.random.permutation(
            jax.random.fold_in(k[2], at), E), E)                    # [E, E]
        rank = jax.random.permutation(
            jax.random.fold_in(k[3], at), E).astype(jnp.float32)
        mlp = out["mlp"]
        out["mlp"] = {
            **mlp,
            "router": jnp.zeros_like(mlp["router"]).at[0, :E].set(
                CODE_WEIGHT * reads),
            "router_bias": (BIAS_SPAN * (2.0 * rank / (E - 1) - 1.0)).astype(
                mlp["router_bias"].dtype)[None]}
        return out

    return {**params, "wte": wte,
            "layers": tuple(coded(at, group)
                            for at, group in enumerate(params["layers"]))}


def reference_forward(params, tokens, config: dict):
    from benchmark.reference import lfm2_moe
    return lfm2_moe.forward(params, tokens, config)


def layer_counts(config: dict) -> dict:
    kinds = pattern(config)
    return {"conv": kinds.count("conv"), "full": kinds.count("full")}


def conv_shape(config: dict) -> dict:
    """What ``costs_conv`` needs of the conv layers."""
    return {"layers": layer_counts(config)["conv"],
            "hidden": config["hidden_size"], "taps": config["conv_L_cache"]}


def attention_shape(config: dict) -> dict:
    return {"layers": layer_counts(config)["full"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["assumed_sizes"]["head_dim"]}


def moe_shape(config: dict) -> dict:
    """The layers that route, the routed experts and an expert's two
    widths."""
    return {"layers": config["num_hidden_layers"]
            - config["num_dense_layers"],
            "experts": config["num_experts"],
            "hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"]}


def kv_bytes_per_token(config: dict) -> int:
    """A cached position: K and V of the ATTENTION layers alone, bf16."""
    shape = attention_shape(config)
    return shape["layers"] * 2 * shape["kv_heads"] * shape["head_dim"] * 2


def tail_bytes_per_slot(config: dict) -> int:
    """What a decode slot keeps for the conv layers: ``conv_L_cache - 1``
    positions of ``hidden_size`` bf16 values a layer, and no state."""
    shape = conv_shape(config)
    return shape["layers"] * (shape["taps"] - 1) * shape["hidden"] * 2


def layer_params(config: dict) -> dict:
    """Parameters by part: a conv operator, an attention operator, the dense
    feed-forward, a router with its bias, one routed expert, two norms a
    layer."""
    D, M = config["hidden_size"], config["moe_intermediate_size"]
    shape = attention_shape(config)
    H = shape["head_dim"]
    return {"conv": 3 * D * D + config["conv_L_cache"] * D + D * D,
            "full": 2 * D * shape["heads"] * H
            + 2 * D * shape["kv_heads"] * H + 2 * H,
            "dense": 3 * D * config["intermediate_size"],
            "router": D * config["num_experts"] + config["num_experts"],
            "expert": 3 * D * M, "norms": 2 * D}


def decode_weight_params(config: dict, experts_hit: float) -> float:
    """Weights one decode step reads: every layer's operator and norms, the
    dense feed-forward, every expert layer's router, the ``experts_hit``
    routed experts the step touched (summed over layers), the final norm and
    the table as the head (the embedding's rows are rows of the same table:
    not counted again)."""
    counts, each = layer_counts(config), layer_params(config)
    dense, layers = config["num_dense_layers"], config["num_hidden_layers"]
    return counts["conv"] * each["conv"] + counts["full"] * each["full"] \
        + layers * each["norms"] + dense * each["dense"] \
        + (layers - dense) * each["router"] + experts_hit * each["expert"] \
        + config["hidden_size"] * (config["vocab_size"] + 1)


def prefill_shape(config: dict) -> dict:
    """What ``costs_prefill.model_operations`` needs: the parameters every
    position meets (the operators, the dense feed-forward, the routers;
    neither norms nor taps nor the bias), one expert's, the attention
    layers' shape and the head's (the table)."""
    counts, each = layer_counts(config), layer_params(config)
    D, shape = config["hidden_size"], attention_shape(config)
    return {"matrix_params":
            counts["conv"] * 4 * D * D
            + counts["full"] * (each["full"] - 2 * shape["head_dim"])
            + config["num_dense_layers"] * each["dense"]
            + moe_shape(config)["layers"] * D * config["num_experts"],
            "expert_params": each["expert"],
            "attention_layers": counts["full"], "heads": shape["heads"],
            "head_dim": shape["head_dim"],
            "head_params": D * config["vocab_size"]}


def weight_params(config: dict) -> float:
    """Every parameter the program holds (the table once: tied)."""
    return decode_weight_params(
        config, moe_shape(config)["layers"] * config["num_experts"])
