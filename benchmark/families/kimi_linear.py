"""Kimi Linear (configs with ``"family": "kimi_linear"``) through
``ray_tpu/models/llama.py``: a stack of two kinds of layer in a period of
four, three Kimi-Delta-Attention layers (the delta rule with a decay a KEY
CHANNEL through a low-rank gate: ``ray_tpu/ops/linear_attention.py``'s
``kda_*``) to one latent (MLA) layer with a direct query projection and NO
positions, a leading dense layer ahead of expert layers that route top-8 of
256 by sigmoid scores with a selection bias, gates renormalised and scaled,
beside a shared expert (``ray_tpu/ops/moe.py``'s dropless path), untied
head.  The configuration is one chip's SHARE of a deployment that divides
every layer's experts over ``expert_share[1]`` chips: ``num_experts`` is the
experts held here (``published.num_experts`` is the router's width), and the
vocabulary is the chip's slice.  Served, a KDA layer keeps a float32 state row
and a convolution tail a decode SLOT beside the latent layers' pages.
"""

from __future__ import annotations

from benchmark.families.xing import CODE_SPARE, CODE_WEIGHT

ENGINE_MODEL = "llama"
# The span of the routing bias (``_with_routing_code``): wide beside Xing's
# 0.01, so that a program that let the bias into the GATES would move them by
# up to a fifth, and narrow beside the margin it has to leave: the code's
# experts score over 0.9 and the others 0.5.
BIAS_SPAN = 0.2


def pattern(config: dict) -> tuple:
    """One period of the stack in the program's names, from the two lists
    of 1-based layer numbers; the layers have to be whole periods of it."""
    lists = config["linear_attn_config"]
    kinds = {n: "linear" for n in lists["kda_layers"]}
    kinds.update({n: "full" for n in lists["full_attn_layers"]})
    count = config["num_hidden_layers"]
    if sorted(kinds) != list(range(1, count + 1)):
        raise ValueError("kda_layers and full_attn_layers have to name "
                         f"every layer 1..{count} once")
    order = [kinds[n] for n in range(1, count + 1)]
    period = order.index("full") + 1 if "full" in order else count
    if count % period or order != order[:period] * (count // period):
        raise ValueError("the layers have to be whole periods of one "
                         "pattern")
    return tuple(order[:period])


def expert_share(config: dict) -> tuple:
    """(this chip's share, the chips that share a layer): the held experts
    times the chips are the router's published width."""
    share, chips = config["expert_share"]
    if config["num_experts"] * chips != config["published"]["num_experts"]:
        raise ValueError("num_experts is the experts held here: a share of "
                         "published.num_experts over expert_share[1] chips")
    return int(share), int(chips)


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("num_nextn_predict_layers", 0), ("num_expert_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("moe_router_activation_func", "sigmoid"),
                      ("hidden_act", "silu"), ("tie_word_embeddings", False),
                      ("q_lora_rank", None), ("rope_scaling", None),
                      ("mla_use_nope", True)):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has a key and a value for "
                         "every head")
    linear = config["linear_attn_config"]
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["moe_intermediate_size"],
        "rope_theta": 0.0,           # mla_use_nope: nothing is rotated
        "rms_eps": config["rms_norm_eps"],
        "num_experts": config["published"]["num_experts"],
        "expert_share": expert_share(config),
        "experts_per_token": config["num_experts_per_token"],
        "norm_topk_prob": config["moe_renormalize"],
        "kv_lora_rank": config["kv_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "first_dense_layers": config["first_k_dense_replace"],
        "dense_mlp_dim": config["intermediate_size"],
        "shared_experts": config["num_shared_experts"],
        "router_scoring": "sigmoid", "router_bias": True,
        "routed_scaling": float(config["routed_scaling_factor"]),
        "layer_pattern": pattern(config),
        "linear_heads": linear["num_heads"],
        "linear_key_dim": linear["head_dim"],
        "linear_value_dim": linear["head_dim"],
        "linear_conv": linear["short_conv_kernel_size"],
        "linear_gate_rank": config["assumed_sizes"]["kda_gate_rank"],
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg):
    """The tree as the engine stores it, so that the replica's one jitted
    call never holds the f32 matrices: bf16 matrices, the routed experts
    among them; f32 norm scales, ``A_log``, ``dt_bias``, router and routing
    bias.  The routing is drawn as a code with wide margins
    (``_with_routing_code``; ``families/xing.py`` says why: the 8th and 9th
    of 256 scores of a random router lie within bf16's rounding of its
    input, and a swapped expert reads as a fault)."""
    from ray_tpu.models.gpt import _cast_leaves
    from ray_tpu.models.llama import llama_init, llama_serving_params
    stored = llama_serving_params(llama_init(rng, cfg), cfg)
    layers = tuple(
        {**group, "mlp": _cast_leaves(group["mlp"], cfg.dtype, "wgu", "wd")}
        for group in stored["layers"])
    return _with_routing_code({**stored, "layers": layers}, rng, cfg)


def _with_routing_code(params, rng, cfg):
    """``families/xing.py::_with_routing_code`` for this tree (a group a
    layer, each a stack of one; a plain residual stream): the first R =
    ``num_experts`` places of the stream carry a token's code (one value at
    ``experts_per_token + CODE_SPARE`` of the R places, 0 at the others), no
    sublayer writes there (those columns of every output projection are 0),
    so every layer's normed input holds one common value at the code's
    places; an expert layer's router reads only those places, each expert its
    own through a seeded permutation, with weight ``CODE_WEIGHT``, and the
    bias, R evenly spaced values over +-``BIAS_SPAN`` in a seeded order,
    chooses which ``experts_per_token`` of the code's experts run: the same
    experts in bfloat16 as in float32.  The router scores all R experts
    whatever the share held here; a quarter of the codes' experts fall on a
    quarter's share."""
    import jax
    import jax.numpy as jnp
    R, hot = cfg.num_experts, cfg.experts_per_token + CODE_SPARE
    V, D = params["wte"].shape
    if R & (R - 1) or hot > R or R > D:
        raise ValueError("the routing code is written for a power of two "
                         f"of experts within the stream's width, not {R}")
    keep = (jnp.arange(D) >= R)                   # the stream's other places
    k = jax.random.split(jax.random.fold_in(rng, 0x726F7574), 4)    # "rout"
    start = jax.random.randint(k[0], (V,), 0, R)
    step = 2 * jax.random.randint(k[1], (V,), 0, R // 2) + 1
    named = (start[:, None] + step[:, None] * jnp.arange(hot)) % R  # [V, hot]
    code = (named[:, :, None] == jnp.arange(R)).any(axis=1)         # [V, R]
    wte = params["wte"].at[:, :R].set(
        (0.02 * (D / R) ** 0.5 * code).astype(params["wte"].dtype))

    def coded(at, group):
        """Layer ``at`` with the code's places written by no sublayer and,
        if it routes, its router reading the code."""
        mixer = "linear" if "linear" in group else "attn"
        out = {**group,
               mixer: {**group[mixer], "wo": group[mixer]["wo"] * keep},
               "mlp": {**group["mlp"], "wd": group["mlp"]["wd"] * keep}}
        if "shared" in group:
            out["shared"] = {**group["shared"],
                             "wd": group["shared"]["wd"] * keep}
        if "router" not in group["mlp"]:
            return out
        reads = jax.nn.one_hot(jax.random.permutation(
            jax.random.fold_in(k[2], at), R), R)                    # [R, R]
        rank = jax.random.permutation(
            jax.random.fold_in(k[3], at), R).astype(jnp.float32)
        mlp = out["mlp"]
        out["mlp"] = {
            **mlp,
            "router": jnp.zeros_like(mlp["router"]).at[0, :R].set(
                CODE_WEIGHT * reads),
            "router_bias": (BIAS_SPAN * (2.0 * rank / (R - 1) - 1.0)).astype(
                mlp["router_bias"].dtype)[None]}
        return out

    return {**params, "wte": wte,
            "layers": tuple(coded(at, group)
                            for at, group in enumerate(params["layers"]))}


def reference_forward(params, tokens, config: dict, with_states=False):
    from benchmark.reference import kimi_linear
    return kimi_linear.forward(params, tokens, config, with_states)


def layer_counts(config: dict) -> dict:
    lists = config["linear_attn_config"]
    return {"linear": len(lists["kda_layers"]),
            "full": len(lists["full_attn_layers"])}


def linear_shape(config: dict) -> dict:
    """What ``costs_kda`` needs of the KDA layers."""
    linear = config["linear_attn_config"]
    return {"layers": layer_counts(config)["linear"],
            "heads": linear["num_heads"], "key_dim": linear["head_dim"],
            "value_dim": linear["head_dim"]}


def moe_shape(config: dict) -> dict:
    """The layers that route, the routed experts HELD here and an expert's
    two widths."""
    return {"layers": config["num_hidden_layers"]
            - config["first_k_dense_replace"],
            "experts": config["num_experts"],
            "hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"]}


def latent_shape(config: dict) -> dict:
    """What ``costs_mla.latent_read`` needs of the latent layers."""
    return {"layers": layer_counts(config)["full"],
            "rank": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "heads": config["num_attention_heads"]}


def kv_bytes_per_token(config: dict) -> int:
    """A cached position: one latent row a LATENT layer (the compressed
    key-value and the shared unrotated key), bf16."""
    shape = latent_shape(config)
    return shape["layers"] * (shape["rank"] + shape["rope"]) * 2


def state_bytes_per_slot(config: dict) -> int:
    """What a decode slot keeps for the KDA layers: a float32 state [heads,
    128, 128] a layer (the convolution's tail, 1.2% of it, is not
    counted)."""
    shape = linear_shape(config)
    return shape["layers"] * shape["heads"] * shape["key_dim"] \
        * shape["value_dim"] * 4


def layer_params(config: dict) -> dict:
    """Parameters a decode step reads whatever was routed, by part: a KDA
    mixer, a latent mixer, the dense feed-forward, an expert layer's shared
    expert and router, one routed expert, two norms a layer."""
    D = config["hidden_size"]
    linear = config["linear_attn_config"]
    N, dh, K = (linear["num_heads"], linear["head_dim"],
                linear["short_conv_kernel_size"])
    r = config["assumed_sizes"]["kda_gate_rank"]
    heads = config["num_attention_heads"]
    nope, rope, v = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                     config["v_head_dim"])
    rank, M = config["kv_lora_rank"], config["moe_intermediate_size"]
    return {"linear": D * 3 * N * dh + K * 3 * N * dh
            + 2 * (D * r + r * N * dh) + N * dh + N + D * N + dh
            + N * dh * D,
            "full": D * heads * (nope + rope) + D * (rank + rope) + rank
            + rank * heads * (nope + v) + heads * v * D,
            "dense": 3 * D * config["intermediate_size"],
            "shared": 3 * D * M * config["num_shared_experts"],
            "router": D * config["published"]["num_experts"]
            + config["published"]["num_experts"],
            "expert": 3 * D * M, "norms": 2 * D}


def decode_weight_params(config: dict, experts_hit: float) -> float:
    """Weights one decode step reads: every layer's mixer and norms, the
    dense feed-forward, every expert layer's shared expert and router, the
    ``experts_hit`` routed experts the step touched (summed over layers),
    the final norm and the head (the embedding is read a row a sequence: not
    counted)."""
    counts, each = layer_counts(config), layer_params(config)
    dense = config["first_k_dense_replace"]
    layers = config["num_hidden_layers"]
    return counts["linear"] * each["linear"] + counts["full"] * each["full"] \
        + layers * each["norms"] + dense * each["dense"] \
        + (layers - dense) * (each["shared"] + each["router"]) \
        + experts_hit * each["expert"] \
        + config["hidden_size"] * (config["vocab_size"] + 1)
