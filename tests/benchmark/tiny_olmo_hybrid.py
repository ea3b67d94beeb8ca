"""The Olmo-Hybrid cell at a size the CPU can run, added to
``tiny.make_root``'s copy of the benchmark the way a later PR adds anything.
Every mechanism of the published configuration is present: eight layers in
two periods of three linear-attention layers and one of full attention, a
value head (192) twice its key head and of a width that folds into panels of
128 lanes, the width-4 convolution, beta up to 2, OLMo 2's output norms and
whole-projection q/k norm, and nothing rotated."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-olmo-hybrid", "serve-olmo-hybrid-decode-wide"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
TINY_HYBRID = {
    "family": "olmo_hybrid", "source": "tests", "model_type": "olmo_hybrid",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 8, "num_attention_heads": 4,
    "num_key_value_heads": 4, "hidden_act": "silu",
    "max_position_embeddings": 65536, "attention_bias": False,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False,
    "layer_types": PERIOD * 2, "linear_num_key_heads": 2,
    "linear_num_value_heads": 2, "linear_key_head_dim": 8,
    "linear_value_head_dim": 192, "linear_conv_kernel_dim": 4,
    "linear_allow_neg_eigval": True, "rope_parameters": {"rope_theta": None},
    "reduced": [], "max_concurrent_queries": 16,
    # bfloat16 at 64 wide through 8 layers of output norms reads 0.1-0.25
    # from the float32 reference (float32 in the program: 1e-5); the limit
    # of the published widths is the configuration file's own
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
                           "tiny-olmo-hybrid.json"), "w") as f:
        json.dump(TINY_HYBRID, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-wide.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-olmo-hybrid", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/tiny-olmo-hybrid.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-olmo-hybrid", "traffic": "tiny-wide",
        "chips": 1, "why": "tests"})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if LIKE in metric.get("workloads", []):
                metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def program(config: dict = TINY_HYBRID, seed: int = 3, **overrides):
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
