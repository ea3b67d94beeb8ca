"""Xing4.0 (configs with ``"family": "xing"``) through
``ray_tpu/models/llama.py``: latent (MLA) attention with YaRN positions and
one pool of latent pages, a four-row hyper-connected residual with
Sinkhorn-projected mixing around every sublayer, leading dense layers ahead
of the expert layers, and in those 64 SwiGLU experts routed top-4 by sigmoid
scores with a selection bias, gates renormalised and scaled, beside a shared
expert (``ray_tpu/ops/moe.py``'s dropless path), untied head.  The
multi-token-prediction module is not held: the family refuses any
``num_nextn_predict_layers`` but 0.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("num_nextn_predict_layers", 0), ("n_group", 1),
                      ("topk_group", 1), ("moe_layer_freq", 1),
                      ("scoring_func", "sigmoid"),
                      ("topk_method", "noaux_tc"), ("hidden_act", "silu"),
                      ("attention_bias", False), ("ep_size", 1),
                      ("tie_word_embeddings", False)):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    scaling = config["rope_scaling"]
    if not scaling or scaling.get("type") != "yarn":
        raise ValueError("models/llama.py runs latent attention with "
                         f"rope_scaling of type 'yarn' only, not {scaling!r}")
    if config["num_key_value_heads"] != config["num_attention_heads"]:
        raise ValueError("latent attention has a key and a value for "
                         "every head")
    if not 0 < config["first_k_dense_replace"] < \
            config["num_hidden_layers"]:
        raise ValueError("first_k_dense_replace must leave at least one "
                         "dense and one expert layer")
    yarn = tuple(float(scaling[k]) for k in (
        "factor", "original_max_position_embeddings", "beta_fast",
        "beta_slow", "mscale", "mscale_all_dim"))
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "mlp_dim": config["moe_intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
        "num_experts": config["n_routed_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "kv_lora_rank": config["kv_lora_rank"],
        "q_lora_rank": config["q_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_yarn": yarn,
        "first_dense_layers": config["first_k_dense_replace"],
        "dense_mlp_dim": config["intermediate_size"],
        "shared_experts": config["n_shared_experts"],
        "router_scoring": "sigmoid", "router_bias": True,
        "routed_scaling": float(config["routed_scaling_factor"]),
        "hc_mult": config["hc_mult"],
        "hc_sinkhorn_iters": config["hc_sinkhorn_iters"],
        "hc_eps": config["hc_eps"],
        "hc_clamp": (float(config["mhc_h_res_clamp_min"]),
                     float(config["mhc_h_res_clamp_max"])),
        "max_seq_len": max_seq_len, **overrides})


# The routing code (``init``): how many experts a token's code names beyond
# the ``num_experts_per_tok`` that are chosen among them, the router's
# weight on the code's places and the span of the bias.
CODE_SPARE, CODE_WEIGHT, BIAS_SPAN = 1, 64.0, 0.01


def init(rng, cfg):
    """The tree as the engine stores it, so that the replica's one jitted
    call never holds the f32 matrices (19 GB at the published size beside
    the 9.6 it keeps): bf16 matrices, the routed experts among them (the
    program reads experts as they are stored); f32 norm scales, router,
    routing bias and hyper-connections.  ``llama_init`` draws the
    hyper-connections' scalars (0.25) and biases (normal, 0.5, the mixing
    matrix's plus twice the identity).

    THE ROUTING IS DRAWN WITH WIDE MARGINS (``_with_routing_code``): with a
    router of random weights the fourth and fifth of 64 scores lie within
    bfloat16's rounding of the router's input on 6-19% of (position, layer)
    pairs, each such pair swaps an expert that carries a quarter of the
    routed output, and the served logits then read 0.02-0.26 from the
    float32 reference by how many pairs swapped: no limit could tell a fault
    or a lower precision from an honest run (PR 34's first readings).  The
    configuration's ``assumed`` says what the draw is and what it does."""
    from ray_tpu.models.gpt import _cast_leaves
    from ray_tpu.models.llama import llama_init, llama_serving_params
    stored = llama_serving_params(llama_init(rng, cfg), cfg)
    stored = {**stored, "layers": {**stored["layers"], "mlp": _cast_leaves(
        stored["layers"]["mlp"], cfg.dtype, "wgu", "wd")}}
    return _with_routing_code(stored, rng, cfg)


def _with_routing_code(params, rng, cfg):
    """Routing by a code the token carries, exact in any precision.  The
    first E = ``num_experts`` values of the residual stream are kept for
    it: a token's embedding holds one value there at
    ``experts_per_token + CODE_SPARE`` experts' places (an arithmetic
    progression mod E from a seeded start by a seeded odd step: E x E / 2
    codes; the value 0.02 sqrt(D / E), so that the code is that many E-ths
    of a row's energy at any width) and 0 at the others'; no sublayer
    writes there (those columns of every output projection are 0), and a
    doubly stochastic ``H_res`` hands the code on, so at every layer the
    code's places hold one common value and the others 0.  A layer's router
    reads only those places, each expert its own through a seeded
    permutation, with weight ``CODE_WEIGHT``: the scores are one value near
    1 at the code's experts, the same bits at each, and 0.5 at the others,
    in bfloat16 as in float32.  The bias, E evenly spaced values over
    +-``BIAS_SPAN`` in a seeded order, then chooses which
    ``experts_per_token`` of the code's experts run: the selection is by
    score + bias and the gates are the equal scores renormalised and
    scaled, as the layer's equations say."""
    import jax
    import jax.numpy as jnp
    E, hot = cfg.num_experts, cfg.experts_per_token + CODE_SPARE
    if E & (E - 1) or hot > E:
        raise ValueError("the routing code is written for a power of two "
                         f"of experts and at most that many named, not {E}")
    V, D = params["wte"].shape
    keep = (jnp.arange(D) >= E)                   # the stream's other places
    k = jax.random.split(jax.random.fold_in(rng, 0x726F7574), 4)    # "rout"
    start = jax.random.randint(k[0], (V,), 0, E)
    step = 2 * jax.random.randint(k[1], (V,), 0, E // 2) + 1
    named = (start[:, None] + step[:, None] * jnp.arange(hot)) % E  # [V, hot]
    code = (named[:, :, None] == jnp.arange(E)).any(axis=1)         # [V, E]
    wte = params["wte"].at[:, :E].set(
        (0.02 * (D / E) ** 0.5 * code).astype(params["wte"].dtype))

    def unwritten(group):
        """``group`` with the code's places written by no sublayer."""
        out = {**group,
               "attn": {**group["attn"], "wo": group["attn"]["wo"] * keep},
               "mlp": {**group["mlp"], "wd": group["mlp"]["wd"] * keep}}
        if "shared" in group:
            out["shared"] = {**group["shared"],
                             "wd": group["shared"]["wd"] * keep}
        return out

    layers = unwritten(params["layers"])
    L = layers["mlp"]["router"].shape[0]
    order = jax.vmap(lambda key: jax.random.permutation(key, E))
    reads = jax.nn.one_hot(order(jax.random.split(k[2], L)), E)  # [L, E, E]
    router = jnp.zeros_like(layers["mlp"]["router"]).at[:, :E].set(
        CODE_WEIGHT * reads)
    rank = order(jax.random.split(k[3], L)).astype(jnp.float32)
    bias = BIAS_SPAN * (2.0 * rank / (E - 1) - 1.0)
    layers["mlp"] = {**layers["mlp"], "router": router,
                     "router_bias": bias.astype(
                         layers["mlp"]["router_bias"].dtype)}
    dense = {"dense_layers": unwritten(params["dense_layers"])} \
        if "dense_layers" in params else {}
    return {**params, **dense, "wte": wte, "layers": layers}


def reference_forward(params, tokens, config: dict, with_gates=False):
    from benchmark.reference import xing
    return xing.forward(params, tokens, config, with_gates)


def moe_shape(config: dict) -> dict:
    """What the expert layers' cost functions and readers need, under this
    configuration's own keys: the layers that route (those after the dense
    ones), their routed experts and an expert's two widths."""
    return {"layers": config["num_hidden_layers"]
            - config["first_k_dense_replace"],
            "experts": config["n_routed_experts"],
            "hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"]}


def kv_bytes_per_token(config: dict) -> int:
    """A cached position: one latent row a layer (the compressed key-value
    and the shared rotated key), bf16."""
    return config["num_hidden_layers"] * (
        config["kv_lora_rank"] + config["qk_rope_head_dim"]) * 2
