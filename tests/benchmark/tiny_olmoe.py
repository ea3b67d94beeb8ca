"""The OLMoE cell at a size the CPU can run, added to ``tiny.make_root``'s
copy of the benchmark the way a later PR adds anything."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-olmoe", "serve-olmoe-decode-heavy"
REFUSED = "tiny-serve-olmoe-refused"     # a configuration no program runs
TINY_OLMOE = {
    "family": "olmoe", "source": "tests", "model_type": "olmoe",
    "vocab_size": 97, "hidden_size": 64, "intermediate_size": 32,
    "num_hidden_layers": 2, "num_attention_heads": 4,
    "num_key_value_heads": 4, "num_experts": 8, "num_experts_per_tok": 3,
    "norm_topk_prob": False, "hidden_act": "silu", "attention_bias": False,
    "clip_qkv": None, "max_position_embeddings": 64, "rms_norm_eps": 1e-5,
    "rope_scaling": None, "rope_theta": 10000, "tie_word_embeddings": False,
    "reduced": [], "max_concurrent_queries": 16,
    "numerics": {"logits_rtol": 0.0625},
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
    configs = {"tiny-olmoe": TINY_OLMOE,
               "tiny-olmoe-bias": {**TINY_OLMOE, "attention_bias": True}}
    for name, config in configs.items():
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json"), "w") as f:
            json.dump(config, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-decode-heavy.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for (name, config), cell in zip(configs.items(), (CELL, REFUSED)):
        bench["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "tests",
            "file": f"benchmark/configs/{name}.json"})
        bench["workloads"].append({
            "name": cell, "config": name, "traffic": "tiny-decode-heavy",
            "chips": 1, "why": "tests"})
        for kind in ("end_to_end", "per_layer"):
            for metric in bench[kind]:
                if LIKE in metric.get("workloads", []):
                    metric["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
