"""Granite 4.0-H (configs with ``"family": "granite_hybrid"``; ``model_type``
``granitemoehybrid``) through ``ray_tpu/models/llama.py``: a stack of two
kinds of layer, Mamba-2 mixers (the state-space rule with ONE key and ONE
query for all heads, a step size that scales the input, a skip, a gate ahead
of ONE norm over all channels: ``_ssm_mixer``, ``ray_tpu/ops/
linear_attention.py``'s ``ssm_*``) nine to one of grouped-query attention
that rotates nothing and scales its scores by ``attention_multiplier``;
experts in EVERY layer, the ``num_experts_per_tok`` largest of the router's
logits kept and softmaxed, beside a shared expert (``ray_tpu/ops/moe.py``'s
dropless path); the embedding, a sublayer's output and the logits under three
more scalars; the head is the table.  The configuration is one chip's SHARE of
a deployment that divides every layer's experts over ``expert_share[1]``
chips: ``num_local_experts`` is the experts held here
(``published.num_local_experts`` is the router's width), and the vocabulary is
the chip's slice.  Served, a Mamba layer keeps a float32 state row and a
convolution tail a decode SLOT beside the attention layers' K/V pages.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"
KINDS = {"mamba": "ssm", "attention": "full"}
# The routing code (``_with_routing_code``): the router's weight on the
# code's places and the span of the code's values (the largest over the
# smallest).  The others' logits are exactly 0 whatever the weight, so it is
# chosen for the GATES: a first layer's kept logits run from ~1.6 to ~3.3,
# their softmax from 0.04 to 0.2, far from even and far from one-hot.
CODE_WEIGHT, CODE_SPAN = 0.25, 2.0


def pattern(config: dict) -> tuple:
    """The stack's kinds in the program's names, as one period (the program
    takes any whole number of periods, the attention layer anywhere in one;
    the cut's ten layers are one)."""
    kinds = config["layer_types"]
    if len(kinds) != config["num_hidden_layers"] or set(kinds) - set(KINDS):
        raise ValueError("layer_types names every layer once, 'mamba' or "
                         "'attention'")
    return tuple(KINDS[kind] for kind in kinds)


def expert_share(config: dict) -> tuple:
    """(this chip's share, the chips that share a layer): the held experts
    times the chips are the router's published width."""
    share, chips = config["expert_share"]
    if config["num_local_experts"] * chips \
            != config["published"]["num_local_experts"]:
        raise ValueError("num_local_experts is the experts held here: a "
                         "share of published.num_local_experts over "
                         "expert_share[1] chips")
    return int(share), int(chips)


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("mamba_n_groups", 1), ("mamba_proj_bias", False),
                      ("mamba_conv_bias", True), ("attention_bias", False),
                      ("position_embedding_type", "nope"),
                      ("rope_scaling", None), ("hidden_act", "silu"),
                      ("normalization_function", "rmsnorm"),
                      ("tie_word_embeddings", True)):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    if config["mamba_expand"] * config["hidden_size"] \
            != config["mamba_n_heads"] * config["mamba_d_head"]:
        raise ValueError("mamba_n_heads heads of mamba_d_head are "
                         "mamba_expand times the hidden size")
    if config["hidden_size"] % config["num_attention_heads"]:
        raise ValueError("LlamaConfig derives head_dim as hidden / heads")
    if config["shared_intermediate_size"] % config["intermediate_size"]:
        raise ValueError("models/llama.py's shared expert is a whole number "
                         "of routed experts wide")
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["intermediate_size"],
        "rope_theta": 0.0,           # "nope": nothing is rotated
        "rms_eps": config["rms_norm_eps"],
        "num_experts": config["published"]["num_local_experts"],
        "expert_share": expert_share(config),
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": True,      # top-k, then a softmax of the kept
        "shared_experts": config["shared_intermediate_size"]
        // config["intermediate_size"],
        "layer_pattern": pattern(config),
        "linear_heads": config["mamba_n_heads"],
        "linear_key_dim": config["mamba_d_state"],
        "linear_value_dim": config["mamba_d_head"],
        "linear_conv": config["mamba_d_conv"],
        "tie_embeddings": True,
        "embedding_multiplier": float(config["embedding_multiplier"]),
        "residual_multiplier": float(config["residual_multiplier"]),
        "attention_multiplier": float(config["attention_multiplier"]),
        "logits_scaling": float(config["logits_scaling"]),
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg, routing_code: bool = True):
    """The tree as the engine stores it, so that the replica's one jitted
    call never holds the f32 matrices (19 GB at the cell's size beside the
    9.5 it keeps): bf16 matrices, the routed experts and the convolution's
    taps among them; f32 norm scales, ``A_log``, ``D``, ``dt_bias``, the
    convolution's bias and the router.  The routing is drawn as a code with
    wide margins (``_with_routing_code``; ``families/xing.py`` says why: the
    10th and 11th of 72 logits of a random router lie within bf16's rounding
    of its input, and a swapped expert reads as a fault)."""
    from ray_tpu.models.gpt import _cast_leaves
    from ray_tpu.models.llama import llama_init, llama_serving_params
    stored = llama_serving_params(llama_init(rng, cfg), cfg)
    layers = tuple(
        {**group, "mlp": _cast_leaves(group["mlp"], cfg.dtype, "wgu", "wd")}
        for group in stored["layers"])
    stored = {**stored, "layers": layers}
    return _with_routing_code(stored, rng, cfg) if routing_code else stored


def _with_routing_code(params, rng, cfg):
    """``families/sdar.py::_with_routing_code`` for this tree (a group a
    layer, each a stack of one; the mixer's way out is ``ssm.wout`` or
    ``attn.wo``; a shared expert) and a router whose width R = 72 is no power
    of two: the first R places of the stream carry a token's code, a value at
    ``experts_per_token`` of the R places (an arithmetic progression mod R
    from a seeded start by a seeded step that shares no factor with R) and 0
    at the others; no sublayer writes there (those columns of every output
    projection are 0), so at every layer the normed input holds at the code's
    places the code's values times one common factor, and 0 at the others.  A
    layer's router reads only those places, each expert its own through a
    seeded permutation, with weight ``CODE_WEIGHT``: the logits are positive
    at the code's experts and exactly 0 at the others, in bfloat16 as in
    float32, and the kept logits are the code's.  The code's values are NOT
    one value: the j-th place of a token's code holds ``CODE_SPAN ** (j / (k
    - 1))`` times the base, so the kept logits differ, their softmax is far
    from even (the gates of this layer's equations, not 1 / k), and a
    program that weighed the experts otherwise would show.  The head is the
    table, so the code's places take part in the logits: the same arithmetic
    on both sides of the check.  Half the code's experts fall on a half's
    share."""
    import math
    import jax
    import jax.numpy as jnp
    R, hot = cfg.num_experts, cfg.experts_per_token
    V, D = params["wte"].shape
    if hot > R or R > D:
        raise ValueError(f"the routing code needs {hot} of {R} places "
                         f"within the stream's width {D}")
    units = jnp.asarray([u for u in range(1, R) if math.gcd(u, R) == 1])
    keep = (jnp.arange(D) >= R)                   # the stream's other places
    k = jax.random.split(jax.random.fold_in(rng, 0x726F7574), 3)    # "rout"
    start = jax.random.randint(k[0], (V,), 0, R)
    step = units[jax.random.randint(k[1], (V,), 0, units.shape[0])]
    named = (start[:, None] + step[:, None] * jnp.arange(hot)) % R  # [V, hot]
    value = CODE_SPAN ** (jnp.arange(hot) / max(hot - 1, 1))        # [hot]
    code = jnp.zeros((V, R), jnp.float32).at[
        jnp.arange(V)[:, None], named].set(
            jnp.broadcast_to(value, named.shape))
    wte = params["wte"].at[:, :R].set(
        (0.02 * (D / R) ** 0.5 * code).astype(params["wte"].dtype))

    def coded(at, group):
        mixer, out_name = ("ssm", "wout") if "ssm" in group else \
            ("attn", "wo")
        reads = jax.nn.one_hot(jax.random.permutation(
            jax.random.fold_in(k[2], at), R), R)                    # [R, R]
        mlp = group["mlp"]
        return {**group,
                mixer: {**group[mixer],
                        out_name: group[mixer][out_name] * keep},
                "shared": {**group["shared"],
                           "wd": group["shared"]["wd"] * keep},
                "mlp": {**mlp, "wd": mlp["wd"] * keep,
                        "router": jnp.zeros_like(mlp["router"]).at[
                            0, :R].set(CODE_WEIGHT * reads)}}

    return {**params, "wte": wte,
            "layers": tuple(coded(at, group)
                            for at, group in enumerate(params["layers"]))}


def reference_forward(params, tokens, config: dict, states_after=None):
    from benchmark.reference import granite_hybrid
    return granite_hybrid.forward(params, tokens, config, states_after)


def layer_counts(config: dict) -> dict:
    kinds = pattern(config)
    return {"ssm": kinds.count("ssm"), "full": kinds.count("full")}


def linear_shape(config: dict) -> dict:
    """What ``costs_linear.state_step`` and ``costs_ssm`` need of the Mamba
    layers: the state a head is [mamba_d_state, mamba_d_head], a key channel
    a row."""
    return {"layers": layer_counts(config)["ssm"],
            "heads": config["mamba_n_heads"],
            "key_dim": config["mamba_d_state"],
            "value_dim": config["mamba_d_head"]}


def attention_shape(config: dict) -> dict:
    return {"layers": layer_counts(config)["full"],
            "heads": config["num_attention_heads"],
            "kv_heads": config["num_key_value_heads"],
            "head_dim": config["hidden_size"]
            // config["num_attention_heads"]}


def moe_shape(config: dict) -> dict:
    """The layers that route (all of them), the routed experts HELD here
    and an expert's two widths."""
    return {"layers": config["num_hidden_layers"],
            "experts": config["num_local_experts"],
            "hidden": config["hidden_size"],
            "width": config["intermediate_size"]}


def kv_bytes_per_token(config: dict) -> int:
    """A cached position: K and V of the ATTENTION layers alone, bf16."""
    shape = attention_shape(config)
    return shape["layers"] * 2 * shape["kv_heads"] * shape["head_dim"] * 2


def conv_channels(config: dict) -> int:
    """The channels the convolution runs over: x | B | C."""
    return config["mamba_n_heads"] * config["mamba_d_head"] \
        + 2 * config["mamba_n_groups"] * config["mamba_d_state"]


def state_bytes_per_slot(config: dict) -> int:
    """What a decode slot keeps for the Mamba layers: a float32 state
    [heads, d_head, d_state] a layer (the convolution's tail, 1.2% of it, is
    ``tail_bytes_per_slot``)."""
    shape = linear_shape(config)
    return shape["layers"] * shape["heads"] * shape["key_dim"] \
        * shape["value_dim"] * 4


def tail_bytes_per_slot(config: dict) -> int:
    """The convolutions' last ``mamba_d_conv - 1`` inputs a slot, bf16."""
    return layer_counts(config)["ssm"] * (config["mamba_d_conv"] - 1) \
        * conv_channels(config) * 2


def layer_params(config: dict) -> dict:
    """Parameters by part: a Mamba mixer, an attention mixer, a layer's
    shared expert, its router, one routed expert, two norms a layer."""
    D, M = config["hidden_size"], config["intermediate_size"]
    heads, C = config["mamba_n_heads"], conv_channels(config)
    inner = heads * config["mamba_d_head"]
    shape = attention_shape(config)
    H = shape["head_dim"]
    return {"ssm": D * (inner + C + heads) + inner * D
            + (config["mamba_d_conv"] + 1) * C + 3 * heads + inner,
            "full": 2 * D * shape["heads"] * H
            + 2 * D * shape["kv_heads"] * H,
            "shared": 3 * D * config["shared_intermediate_size"],
            "router": D * config["published"]["num_local_experts"],
            "expert": 3 * D * M, "norms": 2 * D}


def decode_weight_params(config: dict, experts_hit: float) -> float:
    """Weights one decode step reads: every layer's mixer, norms, shared
    expert and router, the ``experts_hit`` routed experts the step touched
    (summed over layers), the final norm and the table as the head (the
    embedding's rows are rows of the same table: not counted again)."""
    counts, each = layer_counts(config), layer_params(config)
    layers = config["num_hidden_layers"]
    return counts["ssm"] * each["ssm"] + counts["full"] * each["full"] \
        + layers * (each["norms"] + each["shared"] + each["router"]) \
        + experts_hit * each["expert"] \
        + config["hidden_size"] * (config["vocab_size"] + 1)


def weight_params(config: dict) -> float:
    """Every parameter the program holds (the table once: tied)."""
    return decode_weight_params(
        config, config["num_hidden_layers"] * config["num_local_experts"])
