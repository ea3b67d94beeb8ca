"""BENCHMARK.json's Granite 4.0-H configuration against its published
widths, the file's arithmetic against what the program makes, and what its
cell adds to the lists.  Beside ``test_spec.py`` and the other
``test_spec_*.py``, which a PR that brings a configuration may not edit.
Pins no count of the benchmark's cells or configurations, nor the lists'
last entries: the next cell would break it."""

import json
import os

import pytest

from benchmark import spec

CELL = "serve-granite-h-decode-wide"
NAME = "granite-4.0-h-small-10l"
PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
REDUCED = {"num_hidden_layers": 40, "layer_types": PERIOD * 4,
           "num_local_experts": 72, "vocab_size": 100352}
# the widths the issue names, as config.json of ibm-granite/
# granite-4.0-h-small has them in the model-configs catalog
WIDTHS = {"hidden_size": 4096, "mamba_n_heads": 128, "mamba_d_head": 64,
          "mamba_d_state": 128, "mamba_d_conv": 4, "mamba_n_groups": 1,
          "mamba_expand": 2, "intermediate_size": 768,
          "num_experts_per_tok": 10, "shared_intermediate_size": 1536,
          "num_attention_heads": 32, "num_key_value_heads": 8,
          "attention_multiplier": 0.0078125, "embedding_multiplier": 12,
          "residual_multiplier": 0.22, "logits_scaling": 16,
          "position_embedding_type": "nope", "tie_word_embeddings": True,
          "rms_norm_eps": 1e-05, "model_type": "granitemoehybrid"}
# (the traced seconds 3-8 hold no prefill: the three prefill and admission
# readers of PR 57's list find nothing there and the cell is not in theirs)
LISTED = ("decode_batch_occupancy", "decode_device_ms.batch",
          "moe_router_device_ms", "moe_dispatch_combine_device_ms",
          "moe_experts_device_ms", "paged_kv_device_ms.olmoe")
UNLISTED = ("ssm_state_decode_ms", "ssm_conv_decode_ms",
            "ssm_gate_norm_decode_ms", "ssm_proj_decode_ms",
            "ssm_scan_prefill_ms", "ssm_state_roofline",
            "granite_step_hbm_roofline")


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def config():
    return spec.load_json("configs", NAME + ".json")


def test_published_widths(config):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "granite-4.0-h-small"]
        assert row["source_url"] == config["source"]
        assert {k: row["config"][k] for k in REDUCED} == REDUCED
        # every key of the catalog's config stands in the file, and only
        # those that ``reduced`` lists differ
        assert {k: v for k, v in config.items() if k in row["config"]
                and k not in REDUCED} == {
            k: v for k, v in row["config"].items() if k not in REDUCED}
    except FileNotFoundError:          # a checkout without the guides
        pass
    assert {k: config[k] for k in WIDTHS} == WIDTHS
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == REDUCED
    assert {k: config[k] for k in REDUCED} == {
        "num_hidden_layers": 10, "layer_types": PERIOD,
        "num_local_experts": 36, "vocab_size": 50176}
    # the guide's floors: a whole period, 8 experts, an eighth of the
    # vocabulary; two shares of the 72
    assert config["layer_types"].count("attention") == 1
    assert config["vocab_size"] * 2 == REDUCED["vocab_size"]
    assert config["expert_share"] == [0, 2]
    assert set(REDUCED) <= set(config["assumed"])
    for key in ("split_order", "time_step", "mamba_parameters", "gated_norm",
                "positions", "multipliers", "routing", "weights", "memory"):
        assert len(config["assumed"][key]) > 100, key
    assert "eight v5e chips" in config["deployment"]
    assert config["engine"]["page_size"] == 16
    assert config["engine"]["max_prompt_len"] == 512
    assert config["engine"]["max_new_tokens"] == 1024
    numerics = config["numerics"]
    assert numerics["state_dtype"] == "float32"
    assert 0 < numerics["logits_rtol"] < 0.1
    assert 0 < numerics["state_rtol"] < 0.1 and 0 < numerics["tail_rtol"] < 0.1
    assert len(numerics["why"]) > 500


def test_the_files_arithmetic_is_what_the_program_makes(config):
    """By shapes alone (``jax.eval_shape``): the tree the engine stores is
    9.52 GB, a slot's states 37.75 MB, a position 4,096 B."""
    import jax
    from ray_tpu.models import llama
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    assert model.layer_pattern == ("ssm",) * 5 + ("full",) + ("ssm",) * 4
    assert (model.num_experts, model.expert_share, model.experts_per_token,
            model.shared_experts, model.first_dense_layers) \
        == (72, (0, 2), 10, 2, 0)
    stored = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), model))
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(stored))
    assert abs(weight_bytes / 9.52e9 - 1) < 0.005
    assert abs(family.weight_params(config) * 2 / 9.52e9 - 1) < 0.005
    first = stored["layers"][0]
    assert first["ssm"]["win"].shape == (1, 4096, 16768)
    assert first["ssm"]["conv"].shape == (1, 4, 8448)
    assert first["ssm"]["wout"].shape == (1, 8192, 4096)
    assert first["mlp"]["wgu"].shape == (1, 36, 2, 4096, 768)
    assert first["mlp"]["router"].shape == (1, 4096, 72)
    assert first["shared"]["wgu"].shape == (1, 2, 4096, 1536)
    assert stored["layers"][5]["attn"]["wkv"].shape == (1, 4096, 2, 8, 128)
    assert stored["wte"].shape == (50176, 4096) and "lm_head" not in stored
    kp, vp = jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"]))
    slots = engine["max_batch"]
    assert kp.shape == (1, engine["num_pages"], 16, 1024) == vp.v_pages.shape
    assert vp.state.shape == (9, slots, 64, 128, 128)
    assert str(vp.state.dtype) == config["numerics"]["state_dtype"]
    assert vp.conv.shape == (9, slots, 3 * 8448)
    assert vp.state.size * 4 // slots == 37748736 \
        == family.state_bytes_per_slot(config)
    assert family.kv_bytes_per_token(config) == 4096
    # every slot's longest request and the scratch page
    assert engine["num_pages"] == slots * (512 + 1024) // 16 + 1


def test_the_family_refuses_what_the_program_does_not_run(config):
    family = spec.load_part("families", config["family"])
    for key, value in (("mamba_n_groups", 8), ("mamba_proj_bias", True),
                       ("mamba_conv_bias", False), ("attention_bias", True),
                       ("position_embedding_type", "rope"),
                       ("rope_scaling", {"type": "yarn"}),
                       ("tie_word_embeddings", False),
                       ("num_local_experts", 24), ("mamba_expand", 4),
                       ("layer_types", ["mamba"] * 9 + ["window"])):
        with pytest.raises(ValueError, match=key.split("_")[0]):
            family.program_config({**config, key: value}, 64)


def test_what_the_cell_adds_to_the_lists(bench):
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"].endswith(
        "granite-4.0-h-small/blob/main/config.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "decode-heavy-wide-ssm", 1)
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert CELL in served["workloads"]
    reported = {m["name"] for m in spec.metrics_of(bench, "per_layer", CELL)}
    assert reported == set(LISTED)
    assert all(m["moves"] == "served_tokens_per_s"
               for m in spec.metrics_of(bench, "per_layer", CELL))
    assert {m["name"] for m in spec.metrics_of(bench, "end_to_end", CELL)} \
        == {"served_tokens_per_s", "setup_s"}
    assert len(bench["per_layer"]) <= 128      # full: the readers are files


def test_the_unlisted_readers_are_files_with_no_entry(bench):
    from benchmark.tools import read_profile
    names = {m["name"] for kind in ("end_to_end", "per_layer")
             for m in bench[kind]}
    files = {f[:-3] for f in os.listdir(read_profile.UNLISTED)
             if f.endswith(".py")}
    assert set(UNLISTED) <= files
    assert not files & (names | {n.split(".")[0] for n in names})
    for stem in (*UNLISTED, *LISTED):
        assert callable(read_profile.reader(stem.split(".")[0] + ".granite"))


def test_the_cells_traffic_is_the_issues():
    traffic = spec.load_json("traffic", "decode-heavy-wide-ssm.json")
    assert traffic["generator"] == "closed_loop_serve_states"
    assert (traffic["clients"], traffic["block"]) in ((80, 80), (60, 60))
    assert traffic["prompt_tokens"] == {"distribution": "uniform",
                                        "min": 64, "max": 512}
    assert traffic["output_tokens"] == {"distribution": "uniform",
                                        "min": 256, "max": 1024}
    generator = spec.load_part("generators", traffic["generator"])
    assert callable(generator.run)
