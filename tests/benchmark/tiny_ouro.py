"""The Ouro cell at a size the CPU can run, added to ``tiny.make_root``'s
copy of the benchmark the way a later PR adds anything."""

import json
import os

import tiny

CELL, LIKE = "tiny-serve-ouro", "serve-ouro-cot-batch"
REFUSED = "tiny-serve-ouro-refused"      # a configuration no program runs
TINY_OURO = {
    "family": "ouro", "source": "tests", "model_type": "ouro",
    "vocab_size": 256, "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 3, "num_attention_heads": 4,
    "num_key_value_heads": 4, "head_dim": 16, "hidden_act": "silu",
    "layer_types": ["full_attention"] * 3, "max_position_embeddings": 64,
    "max_window_layers": 3, "rms_norm_eps": 1e-6, "rope_scaling": None,
    "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "total_ut_steps": 3,
    "early_exit_threshold": 1, "use_sliding_window": False,
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
    configs = {"tiny-ouro": TINY_OURO,
               "tiny-ouro-exits": {**TINY_OURO, "early_exit_threshold": 0.5}}
    for name, config in configs.items():
        with open(os.path.join(root, "benchmark", "configs",
                               name + ".json"), "w") as f:
            json.dump(config, f)
    with open(os.path.join(root, "benchmark", "traffic",
                           "tiny-cot.json"), "w") as f:
        json.dump(TRAFFIC, f)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    for (name, config), cell in zip(configs.items(), (CELL, REFUSED)):
        bench["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "tests",
            "file": f"benchmark/configs/{name}.json"})
        bench["workloads"].append({
            "name": cell, "config": name, "traffic": "tiny-cot",
            "chips": 1, "why": "tests"})
        for kind in ("end_to_end", "per_layer"):
            for metric in bench[kind]:
                if LIKE in metric.get("workloads", []):
                    metric["workloads"].append(cell)
    with open(path, "w") as f:
        json.dump(bench, f)
    return root
