"""SDAR (configs with ``"family": "sdar"``; ``model_type`` ``sdar_moe``)
through ``ray_tpu/models/llama.py``: RMSNorm, grouped-query attention with a
published ``head_dim`` and a q/k RMSNorm over each head, rotary positions,
128 SwiGLU experts routed top-8 with renormalised gates in every layer
(``ray_tpu/ops/moe.py``'s dropless path), untied head, and generation by
diffusion over blocks: attention causal over blocks and both ways inside one,
a decode step that denoises a block of positions a sequence and commits it
to the pages when it is whole (``llama_block_step``, ``block_unmask``).  The
block settings have no key in ``config.json``; they are the configuration's
``assumed["generation"]``.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"

# The routing code (``init``): the router's weight on the code's places.
CODE_WEIGHT = 64.0
# The q/k norms' scales and the heads' magnitudes (``init``): drawn over
# these spans, so that which values a norm pools, and whether it is there,
# shows in the logits.
NORM_SCALE_SPAN, HEAD_SPAN = (0.5, 1.5), (0.5, 2.0)


def generation(config: dict) -> dict:
    """The block settings as the program's fields name them."""
    g = config["assumed"]["generation"]
    if g["remasking"] not in ("low_confidence_dynamic",
                              "low_confidence_static"):
        raise ValueError("models/llama.py unmasks by confidence, static or "
                         f"dynamic, not by {g['remasking']!r}")
    dynamic = g["remasking"] == "low_confidence_dynamic"
    return {"block_length": g["block_length"],
            "denoise_steps": g["denoising_steps"],
            "confidence_threshold": float(
                g["confidence_threshold"]) if dynamic else 0.0,
            "mask_token": g["mask_token"]}


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("attention_bias", False), ("hidden_act", "silu"),
                      ("tie_word_embeddings", False), ("rope_scaling", None),
                      ("sliding_window", None), ("decoder_sparse_step", 1),
                      ("mlp_only_layers", []), ("norm_topk_prob", True),
                      ("use_sliding_window", False)):
        if config.get(key, runs) != runs:
            raise ValueError(f"models/llama.py runs {key}={runs!r} only, "
                             f"not {config[key]!r}")
    if config["num_attention_heads"] % config["num_key_value_heads"]:
        raise ValueError("query heads share key-value heads in whole groups")
    if not 0 < config["num_experts_per_tok"] <= config["num_experts"]:
        raise ValueError("num_experts_per_tok must be in 1..num_experts")
    return LlamaConfig(**{
        "vocab_size": config["vocab_size"],
        "num_layers": config["num_hidden_layers"],
        "num_heads": config["num_attention_heads"],
        "num_kv_heads": config["num_key_value_heads"],
        "embed_dim": config["hidden_size"],
        "head_size": config["head_dim"],
        "mlp_dim": config["moe_intermediate_size"],
        "rope_theta": float(config["rope_theta"]),
        "rms_eps": config["rms_norm_eps"],
        "num_experts": config["num_experts"],
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "qk_norm_per_head": True, **generation(config),
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg, routing_code: bool = True):
    """The tree as the engine stores it, so that the replica's one jitted
    call never holds float32 matrices (17 GB at the published size beside
    the 8.7 it keeps): bf16 matrices, the experts among them (the program
    reads experts as they are stored); float32 norm scales and router.

    Three things are drawn otherwise than ``llama_init`` draws them, and
    the configuration's ``assumed["seeded_parameters"]`` says so.  The q/k
    norms' scales are uniform over ``NORM_SCALE_SPAN`` and not 1, and each
    head's q and k projection is scaled by a factor from ``HEAD_SPAN``
    (log-uniform), as trained heads differ: a projection of random weights
    has unit RMS in every head already, and a norm left out or pooled over
    all heads would read as rounding.  And THE ROUTING IS DRAWN WITH WIDE
    MARGINS (``_with_routing_code``; ``routing_code=False`` leaves the
    random router, for the reading that says why)."""
    import jax
    import jax.numpy as jnp
    from ray_tpu.models.gpt import _cast_leaves
    from ray_tpu.models.llama import llama_init, llama_serving_params
    params = llama_init(rng, cfg)
    k = jax.random.split(jax.random.fold_in(rng, 0x6E6F726D), 4)    # "norm"
    attn = params["layers"]["attn"]
    L, H = attn["q_norm"].shape
    lo, hi = NORM_SCALE_SPAN

    def heads(key, n):               # a factor a head, log-uniform
        span = jnp.log(jnp.asarray(HEAD_SPAN))
        return jnp.exp(jax.random.uniform(key, (L, n), jnp.float32,
                                          span[0], span[1]))
    attn = {**attn,
            "q_norm": jax.random.uniform(k[0], (L, H), jnp.float32, lo, hi),
            "k_norm": jax.random.uniform(k[1], (L, H), jnp.float32, lo, hi),
            "wq": attn["wq"] * heads(k[2], cfg.num_heads)[:, None, :, None],
            "wkv": attn["wkv"].at[:, :, 0].multiply(
                heads(k[3], cfg.num_kv_heads)[:, None, :, None])}
    params = {**params, "layers": {**params["layers"], "attn": attn}}
    stored = llama_serving_params(params, cfg)
    stored = {**stored, "layers": {**stored["layers"], "mlp": _cast_leaves(
        stored["layers"]["mlp"], cfg.dtype, "wgu", "wd")}}
    return _with_routing_code(stored, rng, cfg) if routing_code else stored


def _with_routing_code(params, rng, cfg):
    """Routing by a code the token carries, exact in any precision
    (``families/xing.py`` has the sigmoid form of it).  The first E =
    ``num_experts`` values of the residual stream are kept for the code: a
    token's embedding holds one value there at ``experts_per_token``
    experts' places (an arithmetic progression mod E from a seeded start by
    a seeded odd step; 0.02 sqrt(D / E), so that the code is that many E-ths
    of a row's energy at any width) and 0 at the others'; no sublayer writes
    there (those columns of every output projection are 0), so at every
    layer the code's places hold one common value, the same bits at each,
    and the others 0.  A layer's router reads only those places, each
    expert its own through a seeded permutation, with weight
    ``CODE_WEIGHT``: the logits are one value at the code's experts and
    exactly 0 at the others, in bfloat16 as in float32, the softmax's k
    largest are the code's experts, and their renormalised gates are 1 / k
    each: the layer's equations, on weights that leave no expert near the
    cut."""
    import jax
    import jax.numpy as jnp
    E, hot = cfg.num_experts, cfg.experts_per_token
    if E & (E - 1):
        raise ValueError("the routing code is written for a power of two "
                         f"of experts, not {E}")
    V, D = params["wte"].shape
    keep = (jnp.arange(D) >= E)                   # the stream's other places
    k = jax.random.split(jax.random.fold_in(rng, 0x726F7574), 3)    # "rout"
    start = jax.random.randint(k[0], (V,), 0, E)
    step = 2 * jax.random.randint(k[1], (V,), 0, E // 2) + 1
    named = (start[:, None] + step[:, None] * jnp.arange(hot)) % E  # [V, hot]
    code = (named[:, :, None] == jnp.arange(E)).any(axis=1)         # [V, E]
    wte = params["wte"].at[:, :E].set(
        (0.02 * (D / E) ** 0.5 * code).astype(params["wte"].dtype))
    layers = params["layers"]
    L = layers["mlp"]["router"].shape[0]
    order = jax.vmap(lambda key: jax.random.permutation(key, E))(
        jax.random.split(k[2], L))
    router = jnp.zeros_like(layers["mlp"]["router"]).at[:, :E].set(
        CODE_WEIGHT * jax.nn.one_hot(order, E))
    layers = {**layers,
              "attn": {**layers["attn"], "wo": layers["attn"]["wo"] * keep},
              "mlp": {**layers["mlp"], "router": router,
                      "wd": layers["mlp"]["wd"] * keep}}
    return {**params, "wte": wte, "layers": layers}


def model_args(config: dict) -> dict:
    """What ``reference.sdar.forward`` takes beside the tree and tokens."""
    return {"rope_theta": float(config["rope_theta"]),
            "rms_eps": config["rms_norm_eps"],
            "top_k": config["num_experts_per_tok"]}


def reference_forward(params, tokens, config: dict, with_gates=False):
    """tokens [S] -> logits [S, V] under the block-causal mask."""
    from benchmark.reference import sdar
    return sdar.forward(params, tokens, generation(config)["block_length"],
                        **model_args(config), with_gates=with_gates)


def reference_generate(params, prompt, n, config: dict, **kw):
    from benchmark.reference import sdar
    g = generation(config)
    return sdar.generate(params, prompt, n, g["block_length"],
                         g["denoise_steps"], g["confidence_threshold"],
                         g["mask_token"], **model_args(config), **kw)


def moe_shape(config: dict) -> dict:
    """What the expert layers' cost functions and readers need, under this
    configuration's own keys: every layer routes."""
    return {"layers": config["num_hidden_layers"],
            "experts": config["num_experts"],
            "hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"]}


def kv_bytes_per_token(config: dict) -> int:
    """A cached position: a key and a value a KV head a layer, bf16."""
    return config["num_hidden_layers"] * 2 * config["num_key_value_heads"] \
        * config["head_dim"] * 2


def step_weight_params(config: dict) -> int:
    """The parameters every block step reads whatever it routes: the
    attention projections and the router of every layer, and the head (the
    embedding is read a row a token; the experts by what is touched)."""
    D, H = config["hidden_size"], config["head_dim"]
    heads, kv = config["num_attention_heads"], config["num_key_value_heads"]
    attention = D * heads * H * 2 + D * kv * H * 2
    return config["num_hidden_layers"] * (
        attention + D * config["num_experts"]) + D * config["vocab_size"]
