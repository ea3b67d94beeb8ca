"""The SDAR cell at a size the CPU can run, added to ``tiny.make_root``'s
copy of the benchmark the way a later PR adds anything.  Every mechanism of
the published configuration is present: a head width that is not hidden /
heads, a q/k norm over each head, grouped-query attention, eight experts
top-2 with renormalised gates in every layer, blocks of four positions
denoised in four passes and committed to the pages when whole."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-sdar", "serve-sdar-block-decode"
TINY_SDAR = {
    "family": "sdar", "source": "tests", "model_type": "sdar_moe",
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 24,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "max_position_embeddings": 4096, "max_window_layers": 2,
    "mlp_only_layers": [], "moe_intermediate_size": 32,
    "norm_topk_prob": True, "num_attention_heads": 4, "num_experts": 8,
    "num_experts_per_tok": 2, "num_hidden_layers": 2,
    "num_key_value_heads": 2, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 256,
    "assumed": {"generation": {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_dynamic", "confidence_threshold": 0.9,
        "mask_token": 255}},
    "reduced": [], "max_concurrent_queries": 16,
    "numerics": {"logits_rtol": 0.03},
    "engine": {"page_size": 8, "max_prompt_len": 32, "max_new_tokens": 16,
               "max_batch": 4, "num_pages": 25},
}
TRAFFIC = {
    "generator": "closed_loop_serve_blocks", "why": "tests", "clients": 6,
    "block": 3,
    "prompt_tokens": {"distribution": "uniform", "min": 8, "max": 32},
    "output_tokens": {"distribution": "uniform", "min": 4, "max": 16}}


def with_generation(config: dict, **changes) -> dict:
    """``config`` with some of its block settings changed."""
    generation = {**config["assumed"]["generation"], **changes}
    return {**config, "assumed": {**config["assumed"],
                                  "generation": generation}}


def make_root(root: str) -> str:
    tiny.make_root(root)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-sdar.json"), "w") as f:
        json.dump(TINY_SDAR, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-blocks.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-sdar", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/tiny-sdar.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-sdar", "traffic": "tiny-blocks",
        "chips": 1, "why": "tests"})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if LIKE in metric.get("workloads", []):
                metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def program(config: dict = TINY_SDAR, seed: int = 3, scale: float = 8.0,
            routing_code: bool = False, **overrides):
    """(the family, the program's float32 configuration, a seeded tree) at
    the tiny size.  The output projections are ``scale`` times the family's
    draw: at this width a sublayer of the family's scale hardly moves the
    stream, every answer is one token repeated, and a fault in the cache
    would change no argmax."""
    import jax
    import jax.numpy as jnp
    from benchmark import spec
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"],
        **{"dtype": jnp.float32, **overrides})
    params = family.init(jax.random.PRNGKey(seed), model,
                         routing_code=routing_code)
    layers = params["layers"]
    layers = {**layers,
              "attn": {**layers["attn"], "wo": layers["attn"]["wo"] * scale},
              "mlp": {**layers["mlp"], "wd": layers["mlp"]["wd"] * scale}}
    return family, model, {**params, "layers": layers}
