"""DeepSeek-V3.2-Exp (configs with ``"family": "deepseek_v32"``) through
``ray_tpu/models/llama.py``: latent (MLA) attention with a query bottleneck
and YaRN positions, beside it the lightning indexer of DeepSeek Sparse
Attention (a key a position in a pool of its own; a query's attention reads
the ``index_topk`` positions that score highest), a leading dense layer ahead
of expert layers that route top-8 of 256 by sigmoid scores with a selection
bias INSIDE the 4 best of 8 groups, gates renormalised and scaled, beside a
shared expert (``ray_tpu/ops/moe.py``'s dropless path), untied head.  The
configuration is one chip's SHARE of a deployment that divides every layer's
experts over ``expert_share[1]`` chips: ``n_routed_experts`` is the experts
held here (``published.n_routed_experts`` is the router's width), and the
vocabulary is the chip's slice.  The multi-token-prediction module is not
held: the family refuses any ``num_nextn_predict_layers`` but 0.
"""

from __future__ import annotations

ENGINE_MODEL = "llama"
# The span of the routing bias (``_with_routing_code``): Kimi's 0.2 and not
# Xing's 0.01, so that a program that let the bias into the GATES moves them
# by up to a fifth; the code's experts score over 0.9 and the others 0.5.
BIAS_SPAN = 0.2


def expert_share(config: dict) -> tuple:
    """(this chip's share, the chips that share a layer): the held experts
    times the chips are the router's published width."""
    share, chips = config["expert_share"]
    if config["n_routed_experts"] * chips != \
            config["published"]["n_routed_experts"]:
        raise ValueError("n_routed_experts is the experts held here: a "
                         "share of published.n_routed_experts over "
                         "expert_share[1] chips")
    return int(share), int(chips)


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.llama import LlamaConfig
    for key, runs in (("num_nextn_predict_layers", 0), ("moe_layer_freq", 1),
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
        "num_experts": config["published"]["n_routed_experts"],
        "expert_share": expert_share(config),
        "expert_groups": (config["n_group"], config["topk_group"]),
        "experts_per_token": config["num_experts_per_tok"],
        "norm_topk_prob": config["norm_topk_prob"],
        "kv_lora_rank": config["kv_lora_rank"],
        "q_lora_rank": config["q_lora_rank"],
        "qk_nope_dim": config["qk_nope_head_dim"],
        "qk_rope_dim": config["qk_rope_head_dim"],
        "v_head_dim": config["v_head_dim"],
        "rope_yarn": yarn,
        "index_heads": config["index_n_heads"],
        "index_head_dim": config["index_head_dim"],
        "index_topk": config["index_topk"],
        "first_dense_layers": config["first_k_dense_replace"],
        "dense_mlp_dim": config["intermediate_size"],
        "shared_experts": config["n_shared_experts"],
        "router_scoring": "sigmoid", "router_bias": True,
        "routed_scaling": float(config["routed_scaling_factor"]),
        "max_seq_len": max_seq_len, **overrides})


def init(rng, cfg):
    """The tree as the engine stores it, so that the replica's one jitted
    call never holds the f32 matrices (18.5 GB at the published size beside
    the 9.3 it keeps): bf16 matrices, the routed experts among them (the
    program reads experts as they are stored); f32 norm scales, the
    indexer's LayerNorm, router and routing bias.  The routing is drawn as a
    code with wide margins (``_with_routing_code``; ``families/xing.py`` says
    why: the 8th and 9th of 256 scores of a random router lie within bf16's
    rounding of its input, and a swapped expert reads as a fault).  The
    indexer is drawn like every other projection, and its LayerNorm's bias
    normal of scale 0.02 (a bias of zeros would hide a program that left it
    out)."""
    import jax
    from ray_tpu.models.gpt import _cast_leaves
    from ray_tpu.models.llama import llama_init, llama_serving_params
    stored = llama_serving_params(llama_init(rng, cfg), cfg)
    stored = {**stored, "layers": {**stored["layers"], "mlp": _cast_leaves(
        stored["layers"]["mlp"], cfg.dtype, "wgu", "wd")}}
    for at, group in enumerate(("dense_layers", "layers")):
        attn = stored[group]["attn"]
        bias = 0.02 * jax.random.normal(
            jax.random.fold_in(rng, 0x6B620000 + at),
            attn["index_k_bias"].shape, attn["index_k_bias"].dtype)
        stored[group] = {**stored[group],
                         "attn": {**attn, "index_k_bias": bias}}
    return _with_index_code(_with_routing_code(stored, rng, cfg), rng, cfg)


# The indexer's code (``_with_index_code``): the weight of the rotated pair
# beside the unrotated column in a head's query.
INDEX_ROTATED = 0.5


def _with_index_code(params, rng, cfg):
    """The indexer drawn so that a query's kept positions do not hang on
    rounding.  With every indexer matrix random, the 2,048th and 2,049th of a
    query's 6k index scores lie within bfloat16's rounding of their inputs
    on one kept position in twenty (the first reading on the chip: 95.4% of
    the reference's kept positions kept by the program), a mean over 2,048
    near-uniform weights moves by a third of its length when 94 of its terms
    are exchanged, and the served logits read 0.079 from the reference where
    a sequence that selects nothing reads 0.019: no limit could tell float8
    from an honest run.  So, as the routing is (``_with_routing_code``), the
    indexer reads the token's CODE, which is the same bits in bfloat16 and
    float32:

    * ``wk`` reads only the stream's code places (random rows of unit scale
      there, zeros elsewhere, so that the LayerNorm's epsilon is nothing
      beside the variance): a position's key before
      its LayerNorm is the code's common value times the sum of nine rows,
      and the LayerNorm takes the value out: the key is a function of the
      token alone;
    * ``weights_proj`` reads the code places with a positive seeded weight a
      head: ``w[t, j] > 0``;
    * column 0 of the attention's ``wq_a`` reads the code places with one
      positive weight (its other 1,535 columns are as drawn), so the normed
      bottleneck's first value is positive at every position, and the
      indexer's ``wq_b`` reads that value alone: head j's query is a positive
      multiple of ONE vector, 1 at the first unrotated column and
      ``INDEX_ROTATED`` at the first rotated pair;
    * so ``I[t, s] = a_t ReLU(k_s[64] + 0.5 (k_s[0] cos(t - s) + k_s[1]
      sin(t - s)))`` with ``a_t > 0``: every one of the 64 heads and 128
      columns is computed as the equations say, the rotation of both sides
      is in the score (pair 0 turns one radian a position), the order of a
      query's scores is that of one number a position, known from the token
      and the distance, and what rounds it is the stored key's bfloat16
      alone.

    What it keeps: every equation, a selection that is scattered over the
    past, differs from query to query (by the distance) and keeps exactly
    2,048.  What it gives up: a selection that depends on the query's
    CONTENT (it depends on its position alone)."""
    import jax
    import jax.numpy as jnp
    R = cfg.num_experts                            # the code's places
    dr = cfg.qk_rope_dim
    k = jax.random.split(jax.random.fold_in(rng, 0x696E6478), 3)    # "indx"

    def coded(at, group):
        attn = group["attn"]
        L, D, rq = attn["wq_a"].shape
        hi, di = cfg.index_heads, cfg.index_head_dim
        places = (jnp.arange(D) < R)[:, None]
        alpha = jax.random.uniform(jax.random.fold_in(k[0], at), (L, hi),
                                   jnp.float32, 0.5, 1.5)
        wq = jnp.zeros((L, rq, hi, di), jnp.float32)
        wq = wq.at[:, 0, :, dr].set(alpha).at[:, 0, :, 0].set(
            INDEX_ROTATED * alpha)
        wk = jax.random.normal(jax.random.fold_in(k[1], at),
                               (L, D, di)) * places
        w = 0.02 * jax.random.uniform(jax.random.fold_in(k[2], at),
                                      (L, 1, hi), jnp.float32, 0.5, 1.5) \
            * places
        wq_a = attn["wq_a"].at[:, :, 0].set(
            (0.02 * places[:, 0]).astype(attn["wq_a"].dtype))
        return {**group, "attn": {
            **attn, "wq_a": wq_a,
            "index_wq": wq.astype(attn["index_wq"].dtype),
            "index_wk": wk.astype(attn["index_wk"].dtype),
            "index_w": w.astype(attn["index_w"].dtype)}}
    return {**params, **{group: coded(at, params[group]) for at, group in
                         enumerate(("dense_layers", "layers"))}}


def _with_routing_code(params, rng, cfg):
    """``families/xing.py::_with_routing_code`` (this tree has its shape: a
    stack of dense layers, a stack of expert layers, a router wider than the
    experts held) with a bias of its own, for a choice inside groups.  The
    first R = ``num_experts`` places of the stream carry a token's code (one
    value at ``experts_per_token + CODE_SPARE`` of the R places, 0 at the
    others), no sublayer writes there, an expert layer's router reads only
    those places, each expert its own, with weight ``CODE_WEIGHT``: the
    scores are one value over 0.9 at the code's experts, the same bits at
    each, and exactly 0.5 at the others, in bfloat16 as in float32.  The
    bias, R values over +-``BIAS_SPAN`` in a seeded order (evenly spaced,
    each moved by a seeded quarter of the spacing at most, so that no two
    PAIRS of them have the same sum: a group's mark is the sum of its two
    largest score + bias, and two marks that tie exactly would be told apart
    by rounding), then decides everything the equations leave to it: which 4
    of the 8 groups are kept (a group's mark grows with the code's experts
    in it), which of the code's experts in them run, and which of the others
    fill the 8 where the kept groups hold fewer."""
    import jax
    import jax.numpy as jnp
    from benchmark.families import xing
    R = cfg.num_experts
    if R > params["wte"].shape[1]:
        raise ValueError("the routing code's places are the stream's first "
                         f"{R}: the stream is narrower")
    params = xing._with_routing_code(params, rng, cfg)
    mlp = params["layers"]["mlp"]
    L = mlp["router_bias"].shape[0]
    k = jax.random.split(jax.random.fold_in(rng, 0x62696173), 2)    # "bias"
    rank = jax.vmap(lambda key: jax.random.permutation(key, R))(
        jax.random.split(k[0], L)).astype(jnp.float32) \
        + jax.random.uniform(k[1], (L, R), jnp.float32, -0.25, 0.25)
    bias = BIAS_SPAN * (2.0 * rank / (R - 1) - 1.0)
    return {**params, "layers": {**params["layers"], "mlp": {
        **mlp, "router_bias": bias.astype(mlp["router_bias"].dtype)}}}


def reference_forward(params, tokens, config: dict, **more):
    from benchmark.reference import deepseek_v32
    return deepseek_v32.forward(params, tokens, config, **more)


def moe_shape(config: dict) -> dict:
    """The layers that route, the routed experts HELD here and an expert's
    two widths."""
    return {"layers": config["num_hidden_layers"]
            - config["first_k_dense_replace"],
            "experts": config["n_routed_experts"],
            "hidden": config["hidden_size"],
            "width": config["moe_intermediate_size"]}


def latent_row_bytes(config: dict) -> int:
    """A position's latent row as the pool STORES it: ``kv_lora_rank +
    qk_rope_head_dim`` in whole 128-lane tiles, bf16."""
    width = config["kv_lora_rank"] + config["qk_rope_head_dim"]
    return -(-width // 128) * 128 * 2


def kv_bytes_per_token(config: dict) -> int:
    """A cached position: one latent row and one indexer key a layer, as
    stored."""
    return config["num_hidden_layers"] * (
        latent_row_bytes(config) + config["index_head_dim"] * 2)


def dsa_shape(config: dict) -> dict:
    """What ``costs_dsa`` needs of the sparse attention."""
    return {"layers": config["num_hidden_layers"],
            "heads": config["num_attention_heads"],
            "rank": config["kv_lora_rank"],
            "rope": config["qk_rope_head_dim"],
            "index_heads": config["index_n_heads"],
            "index_dim": config["index_head_dim"],
            "row_bytes": latent_row_bytes(config),
            "key_bytes": config["index_head_dim"] * 2}


def layer_params(config: dict) -> dict:
    """Parameters by part: latent attention, the indexer, the dense
    feed-forward, a router with its bias, one expert (routed or shared), two
    norms a layer."""
    D, M = config["hidden_size"], config["moe_intermediate_size"]
    N, rq, rkv = (config["num_attention_heads"], config["q_lora_rank"],
                  config["kv_lora_rank"])
    nope, rope, dv = (config["qk_nope_head_dim"], config["qk_rope_head_dim"],
                      config["v_head_dim"])
    hi, di = config["index_n_heads"], config["index_head_dim"]
    routed = config["published"]["n_routed_experts"]
    return {"attention": D * rq + rq + rq * N * (nope + rope)
            + D * (rkv + rope) + rkv + rkv * N * (nope + dv) + N * dv * D,
            "indexer": rq * hi * di + D * di + 2 * di + D * hi,
            "dense": 3 * D * config["intermediate_size"],
            "router": D * routed + routed,
            "expert": 3 * D * M, "norms": 2 * D}


def decode_weight_params(config: dict, experts_hit: float) -> float:
    """Weights one decode step reads: every layer's attention, indexer and
    norms, the dense feed-forward, every expert layer's router and shared
    expert, the ``experts_hit`` routed experts the step touched (summed over
    layers), the final norm and the head (the embedding's few rows are not
    counted)."""
    each = layer_params(config)
    dense, layers = (config["first_k_dense_replace"],
                     config["num_hidden_layers"])
    return layers * (each["attention"] + each["indexer"] + each["norms"]) \
        + dense * each["dense"] + (layers - dense) * (
            each["router"] + config["n_shared_experts"] * each["expert"]) \
        + experts_hit * each["expert"] \
        + config["hidden_size"] * (config["vocab_size"] + 1)


def weight_params(config: dict) -> float:
    """Every parameter the program holds (embedding and head apart)."""
    return decode_weight_params(
        config, moe_shape(config)["layers"] * config["n_routed_experts"]) \
        + config["hidden_size"] * config["vocab_size"]

