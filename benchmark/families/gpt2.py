"""GPT-2 (configs with ``"family": "gpt2"``) through ``ray_tpu/models/gpt.py``.

The configuration file holds the published ``config.json`` keys; this maps
them to the program's ``GPTConfig`` and counts what ``costs.py`` needs.
Departure of the program from the published model, kept by the reference:
no bias on the q, k, v projection.
"""

from __future__ import annotations

ENGINE_MODEL = "gpt"


def program_config(config: dict, max_seq_len: int, **overrides):
    from ray_tpu.models.gpt import GPTConfig
    if config.get("n_inner") not in (None, 4 * config["n_embd"]):
        raise ValueError("GPTConfig has a feed-forward of 4 x n_embd only")
    return GPTConfig(vocab_size=config["vocab_size"],
                     num_layers=config["n_layer"],
                     num_heads=config["n_head"],
                     embed_dim=config["n_embd"], max_seq_len=max_seq_len,
                     **overrides)


def train_config(config: dict, seq: int):
    return program_config(config, seq, **config["train"]["model"])


def init(rng, cfg):
    from ray_tpu.models.gpt import gpt_init
    return gpt_init(rng, cfg)


def param_axes(cfg):
    from ray_tpu.models.gpt import gpt_param_axes
    return gpt_param_axes(cfg)


def make_train_step(cfg, tx, rules):
    from ray_tpu.models.gpt import make_train_step
    return make_train_step(cfg, tx, rules)


def loss(params, batch, cfg, rules):
    from ray_tpu.models.gpt import gpt_loss
    return gpt_loss(params, batch, cfg, rules)


def reference_loss(params, tokens, config: dict):
    from benchmark.reference import gpt2
    return gpt2.loss(params, tokens)


def matmul_params(config: dict) -> int:
    """Parameters inside matrix products: the tied embedding (the head),
    and per layer qkv, attention output and the two feed-forward maps."""
    d, layers = config["n_embd"], config["n_layer"]
    return config["vocab_size"] * d + layers * 12 * d * d


def attention_shape(config: dict) -> dict:
    return {"layers": config["n_layer"], "heads": config["n_head"],
            "head_dim": config["n_embd"] // config["n_head"],
            "embed": config["n_embd"]}


def reference_forward(params, tokens, config: dict):
    from benchmark.reference import gpt2
    return gpt2.forward(params, tokens)
