"""The LFM2 cell at a size the CPU can run, added to ``tiny.make_root``'s copy
of the benchmark the way a later PR adds anything.  Every mechanism of the
published configuration is present: six layers (a leading dense conv layer,
then ``full, conv, conv, conv, full`` with experts), gated short convolutions
over 3 positions with no activation, grouped-query attention with 4 / 2 heads
of 16 normed each head by itself and rotated, 8 experts top-4 by sigmoid
scores with a bias renormalised over their sum + 1e-6, no shared expert, a
tied head."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-lfm2", "serve-lfm2-longprompt-wide"
TINY_LFM2 = {
    "family": "lfm2_moe", "source": "tests", "model_type": "lfm2_moe",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 16, "num_hidden_layers": 6,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv",
                    "full_attention"],
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "assumed_sizes": {"head_dim": 16}, "conv_L_cache": 3, "conv_bias": False,
    "num_dense_layers": 1, "num_experts": 8, "num_experts_per_tok": 4,
    "norm_topk_prob": True, "use_expert_bias": True,
    "routed_scaling_factor": 1, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "max_position_embeddings": 128000,
    "reduced": [], "max_concurrent_queries": 16,
    # bfloat16 at 64 wide through 6 layers reads 0.02-0.1 from the float32
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
                           "tiny-lfm2.json"), "w") as f:
        json.dump(TINY_LFM2, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-longprompt-wide.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-lfm2", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/tiny-lfm2.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-lfm2",
        "traffic": "tiny-longprompt-wide", "chips": 1, "why": "tests"})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if LIKE in metric.get("workloads", []):
                metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def program(config: dict = TINY_LFM2, seed: int = 3, **overrides):
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
