"""The Granite 4.0-H cell at a size the CPU can run, added to
``tiny.make_root``'s copy of the benchmark the way a later PR adds anything.
Every mechanism of the published configuration is present: three layers in
one period with the attention layer in the MIDDLE of it (a Mamba-2 layer, one
of grouped-query attention without positions and a scale of its own, another
Mamba-2 layer: a tiny engine of this model costs 3-4 s of tracing a layer on
the CPU), heads of 16 values eight to a 128-lane panel, experts in EVERY layer
that keep the 4 largest of 12 logits and softmax those, one share of two held
(experts 6-11: not the first, so the offset counts), a shared expert twice an
expert wide, the four multipliers, a tied head, a slice of a vocabulary."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-granite-h", "serve-granite-h-decode-wide"
TINY_GRANITE = {
    "family": "granite_hybrid", "source": "tests",
    "model_type": "granitemoehybrid", "vocab_size": 256, "hidden_size": 64,
    "intermediate_size": 16, "shared_intermediate_size": 32,
    "num_hidden_layers": 3,
    "layer_types": ["mamba", "attention", "mamba"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "mamba_n_heads": 8, "mamba_d_head": 16, "mamba_d_state": 16,
    "mamba_d_conv": 4, "mamba_n_groups": 1, "mamba_expand": 2,
    "mamba_conv_bias": True, "mamba_proj_bias": False,
    "mamba_chunk_size": 256, "num_local_experts": 6,
    "num_experts_per_tok": 4, "attention_multiplier": 0.0625,
    "embedding_multiplier": 12, "residual_multiplier": 0.22,
    "logits_scaling": 16, "rms_norm_eps": 1e-5, "hidden_act": "silu",
    "attention_bias": False, "normalization_function": "rmsnorm",
    "position_embedding_type": "nope", "rope_scaling": None,
    "rope_theta": 10000, "tie_word_embeddings": True,
    "published": {"num_local_experts": 12, "vocab_size": 1024,
                  "num_hidden_layers": 40},
    "expert_share": [1, 2], "reduced": [], "max_concurrent_queries": 16,
    # bfloat16 at 64 wide through 3 layers reads 0.01-0.05 from the float32
    # reference (float32 in the program: 1e-6); the limits of the published
    # widths are the configuration file's own
    "numerics": {"logits_rtol": 0.5, "state_rtol": 0.5, "tail_rtol": 0.5,
                 "state_dtype": "float32"},
    "engine": {"page_size": 8, "max_prompt_len": 32, "max_new_tokens": 16,
               "max_batch": 4, "num_pages": 25},
}
TRAFFIC = {
    "generator": "closed_loop_serve_states", "why": "tests", "clients": 6,
    "block": 3,
    "prompt_tokens": {"distribution": "uniform", "min": 8, "max": 32},
    "output_tokens": {"distribution": "uniform", "min": 4, "max": 16}}


def make_root(root: str) -> str:
    tiny.make_root(root)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-granite-h.json"), "w") as f:
        json.dump(TINY_GRANITE, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-wide-ssm.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-granite-h", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/tiny-granite-h.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-granite-h", "traffic": "tiny-wide-ssm",
        "chips": 1, "why": "tests"})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if LIKE in metric.get("workloads", []):
                metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def program(config: dict = TINY_GRANITE, seed: int = 3, **overrides):
    """(the family, the program's float32 configuration, a seeded tree as
    the family stores it) at the tiny size."""
    import jax
    import jax.numpy as jnp
    from benchmark import spec
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"],
        **{"dtype": jnp.float32, "attention": "dense", **overrides})
    return family, model, jax.jit(lambda key: family.init(key, model))(
        jax.random.PRNGKey(seed))
