"""The DeepSeek-V3.2-Exp cell at a size the CPU can run, added to
``tiny.make_root``'s copy of the benchmark the way a later PR adds anything.
Every mechanism of the published configuration is present: three layers (a
leading dense one, then two with experts), latent attention through a query
bottleneck with YaRN positions, the lightning indexer (4 heads of 16, the
first 8 columns rotated, 8 positions kept a query, so that every query past
the eighth position selects), 16 routed experts top-4 inside 2 of 4 groups
of which this program holds a quarter, a shared expert, an untied head, and
prompts that run as chunks of 16 positions over pages of 8."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-dsv32", "serve-dsv32-longctx-mixed"
TINY_DSV32 = {
    "family": "deepseek_v32", "source": "tests", "model_type": "deepseek_v32",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 16, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "num_attention_heads": 4,
    "num_key_value_heads": 4, "q_lora_rank": 24, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "index_n_heads": 4, "index_head_dim": 16, "index_topk": 8,
    "n_routed_experts": 4, "expert_share": [0, 4],
    "published": {"n_routed_experts": 16},
    "n_shared_experts": 1, "num_experts_per_tok": 4, "n_group": 4,
    "topk_group": 2, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc",
    "num_nextn_predict_layers": 0, "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16, "type": "yarn"},
    "reduced": [], "max_concurrent_queries": 16,
    # bfloat16 at 64 wide through 3 layers, with a selection that bfloat16
    # and float32 may draw differently (float32 in the program: 1e-5); the
    # limit of the published widths is the configuration file's own
    "numerics": {"logits_rtol": 0.5, "logits_rtol_selecting": 0.5,
                 "selection_common_min": 0.5},
    "engine": {"page_size": 8, "max_prompt_len": 48, "max_new_tokens": 16,
               "max_batch": 4, "num_pages": 33, "prefill_chunk": 16},
}
TRAFFIC = {
    "generator": "closed_loop_serve_longctx", "why": "tests", "clients": 6,
    "block": 3,
    "prompt_tokens": {"distribution": "uniform", "min": 8, "max": 48},
    "output_tokens": {"distribution": "uniform", "min": 4, "max": 16}}


def make_root(root: str) -> str:
    tiny.make_root(root)
    with open(os.path.join(root, "benchmark", "configs",
                           "tiny-dsv32.json"), "w") as f:
        json.dump(TINY_DSV32, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-longctx-mixed.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({
        "name": "tiny-dsv32", "source": "tests", "reduced": [],
        "why": "tests", "file": "benchmark/configs/tiny-dsv32.json"})
    bench["workloads"].append({
        "name": CELL, "config": "tiny-dsv32",
        "traffic": "tiny-longctx-mixed", "chips": 1, "why": "tests"})
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if LIKE in metric.get("workloads", []):
                metric["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root


def program(config: dict = TINY_DSV32, seed: int = 3, **overrides):
    """(the family, the program's float32 configuration, a seeded tree as
    the family stores it) at the tiny size."""
    import jax
    import jax.numpy as jnp
    from benchmark import spec
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"],
        **{"dtype": jnp.float32, **overrides})
    # (one traced program, not an operation at a time: a third of the time)
    return family, model, jax.jit(lambda key: family.init(key, model))(
        jax.random.PRNGKey(seed))
