"""A copy of the benchmark with cells of a size the CPU can run, added the
way a later PR adds anything: as new files and new entries, with no edit to
a file that is there."""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_GPT2 = {
    "family": "gpt2", "source": "tests", "vocab_size": 97, "n_positions": 64,
    "n_embd": 32, "n_layer": 2, "n_head": 2, "n_inner": None,
    "reduced": [], "mesh": {},
    "train": {"batch": 4, "model": {"attention": "flash", "remat": True,
                                    "remat_policy": "dots", "ce_block": 32}},
}
TINY_MISTRAL = {
    "family": "mistral", "source": "tests", "vocab_size": 97,
    "hidden_size": 64, "intermediate_size": 96, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "rms_norm_eps": 1e-5, "rope_theta": 1e6, "sliding_window": None,
    "reduced": [], "max_concurrent_queries": 16,
    "numerics": {"logits_rtol": 5 * 2.0 ** -8},
    "engine": {"page_size": 8, "max_prompt_len": 32, "max_new_tokens": 16,
               "max_batch": 4, "num_pages": 25},
}
TRAFFIC = {
    "tiny-chat": {
        "generator": "open_loop_serve", "why": "tests", "rate_per_s": 4.0,
        "prompt_tokens": {"distribution": "lognormal", "median": 12,
                          "sigma": 0.6, "min": 4, "max": 32},
        "output_tokens": {"distribution": "lognormal", "median": 6,
                          "sigma": 0.5, "min": 2, "max": 16}},
    "tiny-batch": {
        "generator": "closed_loop_serve", "why": "tests", "clients": 6,
        "block": 3,
        "prompt_tokens": {"distribution": "uniform", "min": 16, "max": 32},
        "output_tokens": {"distribution": "uniform", "min": 2, "max": 8}},
    "tiny-pretrain": {
        "generator": "train_steps", "why": "tests", "seq_len": 64,
        "fetch_loss_every": 2, "traced_steps": 2},
}
# one of each kind a later PR may add: a family, a generator, a metric
NEW_FAMILY = "from benchmark.families.gpt2 import *   # noqa: F401,F403\n"
NEW_GENERATOR = "from benchmark.generators.train_steps import run  # noqa\n"
NEW_METRIC = ('"""Steps finished in the window."""\n\n\n'
              "def read(run):\n    return run['steps']\n")
CELLS = {  # workload -> (config, traffic, chips)
    "tiny-serve-chat": ("tiny-mistral", "tiny-chat", 1),
    "tiny-serve-batch": ("tiny-mistral", "tiny-batch", 1),
    "tiny-train": ("tiny-gpt2", "tiny-pretrain", 1),
    "tiny-train-mesh": ("tiny-gpt2-mesh", "tiny-pretrain", 4),
    "tiny-train-added": ("tiny-gpt2-added", "tiny-pretrain-added", 1),
}


def make_root(root: str) -> str:
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)

    def write(data, *parts):
        path = os.path.join(root, "benchmark", *parts)
        with open(path, "w") as f:
            f.write(data if isinstance(data, str) else json.dumps(data))

    configs = {"tiny-gpt2": TINY_GPT2, "tiny-mistral": TINY_MISTRAL,
               "tiny-gpt2-mesh": {**TINY_GPT2,
                                  "mesh": {"fsdp": 2, "tp": 2}},
               "tiny-gpt2-added": {**TINY_GPT2, "family": "added_family"}}
    for name, config in configs.items():
        write(config, "configs", name + ".json")
        bench["configs"].append({
            "name": name, "source": "tests", "reduced": [], "why": "tests",
            "file": f"benchmark/configs/{name}.json"})
    for name, traffic in TRAFFIC.items():
        write(traffic, "traffic", name + ".json")
    write({**TRAFFIC["tiny-pretrain"], "generator": "added_generator"},
          "traffic", "tiny-pretrain-added.json")
    write(NEW_FAMILY, "families", "added_family.py")
    write(NEW_GENERATOR, "generators", "added_generator.py")
    write(NEW_METRIC, "metrics", "added_steps.py")
    peaks = json.load(open(os.path.join(root, "benchmark", "peaks.json")))
    peaks["cpu"] = {"platform": "cpu", "bf16_flops_per_s": 1e12,
                    "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10,
                    "source": "tests: not a device"}
    write(peaks, "peaks.json")

    serve = {"tiny-serve-chat", "tiny-serve-batch"}
    renamed = {}
    for workload, (config, traffic, chips) in CELLS.items():
        bench["workloads"].append({"name": workload, "config": config,
                                   "traffic": traffic, "chips": chips,
                                   "why": "tests"})
        like = ("serve-chat-steady" if workload == "tiny-serve-chat" else
                "serve-longprompt-batch" if workload in serve else
                "train-gpt2-large-fsdp2tp2" if chips == 4 else
                "train-gpt2-medium-1chip")
        renamed.setdefault(like, []).append(workload)
    for kind in ("end_to_end", "per_layer"):
        for metric in bench[kind]:
            if "workloads" in metric:
                metric["workloads"] = metric["workloads"] + [
                    w for like in metric["workloads"]
                    for w in renamed.get(like, [])]
    bench["per_layer"].append({
        "name": "added_steps", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "models step",
        "moves": "train_tokens_per_s_chip",
        "workloads": ["tiny-train-added"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root


def run_cell(root: str, workload: str, trace: int, seconds: float = 3,
             seed: int = 2 ** 31 + 7, chips: int = 1):
    """One run of a tiny cell on virtual CPU devices that a node advertises
    as chips; returns (exit code, last stdout line parsed, stderr)."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
           "RT_NUM_TPU_CHIPS": str(chips),
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={chips}",
           "JAX_COMPILATION_CACHE_DIR": os.path.join(root, "cache")}
    r = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", workload, "--seed", str(seed),
         "--seconds", str(seconds), "--trace", str(trace)],
        env=env, capture_output=True, text=True, timeout=420, cwd=root)
    lines = r.stdout.strip().splitlines()
    line = json.loads(lines[-1]) if r.returncode == 0 and lines else None
    return r.returncode, line, r.stderr
