"""The Kimi Linear cell at a size the CPU can run, added to
``tiny.make_root``'s copy of the benchmark the way a later PR adds anything.
Every mechanism of the published configuration is present: eight layers in
two periods of three KDA layers (a decay a key channel through a bottleneck,
the width-4 convolution, the sigmoid-gated norm) and one latent layer with a
direct query projection and no positions, a leading dense layer, then expert
layers that route top-4 of 32 by sigmoid scores with a bias and hold one
share of four (experts 8-15: not the first, so the offset counts), a shared
expert, a slice of a vocabulary."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-kimi-linear", "serve-kimi-linear-reasoning-wide"
LINEAR = {"kda_layers": [1, 2, 3, 5, 6, 7], "full_attn_layers": [4, 8],
          "head_dim": 8, "num_heads": 4, "short_conv_kernel_size": 4}
TINY_KIMI = {
    "family": "kimi_linear", "source": "tests", "model_type": "kimi_linear",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 16, "num_hidden_layers": 8,
    "num_attention_heads": 4, "num_key_value_heads": 4, "head_dim": 16,
    "hidden_act": "silu", "first_k_dense_replace": 1, "kv_lora_rank": 24,
    "q_lora_rank": None, "qk_nope_head_dim": 8, "qk_rope_head_dim": 8,
    "v_head_dim": 8, "mla_use_nope": True, "rope_scaling": None,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "tie_word_embeddings": False,
    "linear_attn_config": LINEAR, "num_experts": 8,
    "num_experts_per_token": 4, "num_shared_experts": 1,
    "moe_renormalize": True, "moe_router_activation_func": "sigmoid",
    "moe_layer_freq": 1, "num_expert_group": 1, "topk_group": 1,
    "use_grouped_topk": True, "routed_scaling_factor": 2.446,
    "num_nextn_predict_layers": 0,
    "published": {"num_experts": 32, "vocab_size": 1024,
                  "num_hidden_layers": 27},
    "expert_share": [1, 4], "assumed_sizes": {"kda_gate_rank": 8},
    "reduced": [], "max_concurrent_queries": 16,
    # bfloat16 at 64 wide through 8 layers reads 0.02-0.1 from the float32
    # reference (float32 in the program: 1e-6); the limit of the published
    # widths is the configuration file's own
    "numerics": {"logits_rtol": 0.5},
    "engine": {"page_size": 8, "max_prompt_len": 32, "max_new_tokens": 16,
               "max_batch": 4, "num_pages": 25},
}
TRAFFIC = {
    "generator": "closed_loop_serve_checked", "why": "tests", "clients": 6,
    "block": 3,
    "prompt_tokens": {"distribution": "uniform", "min": 8, "max": 32},
    "output_tokens": {"distribution": "uniform", "min": 4, "max": 16}}


def make_root(root: str) -> str:
    tiny.make_root(root)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-kimi-linear.json"), "w") as f:
        json.dump(TINY_KIMI, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-wide.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-kimi-linear", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/tiny-kimi-linear.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-kimi-linear", "traffic": "tiny-wide",
        "chips": 1, "why": "tests"})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if LIKE in metric.get("workloads", []):
                metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def program(config: dict = TINY_KIMI, seed: int = 3, **overrides):
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
    return family, model, family.init(jax.random.PRNGKey(seed), model)
