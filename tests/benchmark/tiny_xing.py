"""The Xing cell at a size the CPU can run, added to ``tiny.make_root``'s
copy of the benchmark the way a later PR adds anything.  Every mechanism of
the published configuration is present: latent attention with unequal q/k
and v heads and YaRN, a hyper-connected residual of four rows, one dense
layer ahead of two expert layers, eight experts top-2 by sigmoid scores with
a selection bias, a shared expert."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-xing", "serve-xing-reasoning-batch"
REFUSED = "tiny-serve-xing-refused"      # a configuration no program runs
TINY_XING = {
    "family": "xing", "source": "tests", "model_type": "xing4_0",
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
    "hidden_act": "silu", "hidden_size": 64, "intermediate_size": 96,
    "kv_lora_rank": 32, "max_position_embeddings": 4096,
    "moe_intermediate_size": 32, "moe_layer_freq": 1, "n_group": 1,
    "n_routed_experts": 8, "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 4, "num_experts_per_tok": 2,
    "num_hidden_layers": 3, "num_key_value_heads": 4,
    "num_nextn_predict_layers": 0, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-6, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 48, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "rms_norm_eps": 1e-6, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 16,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 12, "vocab_size": 256,
    "reduced": [], "max_concurrent_queries": 16,
    "numerics": {"logits_rtol": 0.03},
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
    configs = {"tiny-xing": TINY_XING,
               "tiny-xing-mtp": {**TINY_XING,
                                 "num_nextn_predict_layers": 1}}
    for name, config in configs.items():
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json"), "w") as f:
            json.dump(config, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-reasoning.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for (name, config), cell in zip(configs.items(), (CELL, REFUSED)):
        bench["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "tests",
            "file": f"benchmark/configs/{name}.json"})
        bench["workloads"].append({
            "name": cell, "config": name, "traffic": "tiny-reasoning",
            "chips": 1, "why": "tests"})
        for kind in ("end_to_end", "per_layer"):
            for metric in bench[kind]:
                if LIKE in metric.get("workloads", []):
                    metric["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
