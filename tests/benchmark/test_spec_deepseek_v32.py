"""BENCHMARK.json's DeepSeek-V3.2-Exp configuration against its published
widths, the file's arithmetic against what the program makes, and what its
cell adds to the lists.  Beside ``test_spec.py`` and the other
``test_spec_*.py``, which a PR that brings a configuration may not edit.
Pins no count of the benchmark's cells or configurations, nor the lists'
last entries: the next cell would break it."""

import json
import os

import pytest

from benchmark import spec

CELL = "serve-dsv32-longctx-mixed"
NAME = "deepseek-v3.2-exp-5l"
REDUCED = {"num_hidden_layers": 61, "first_k_dense_replace": 3,
           "n_routed_experts": 256, "vocab_size": 129280,
           "num_nextn_predict_layers": 1}
# config.json of deepseek-ai/DeepSeek-V3.2-Exp as the model-configs catalog
# has it: every key of it stands in the configuration's file, and only those
# that ``reduced`` lists differ.
PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 3,
    "hidden_act": "silu", "hidden_size": 7168, "index_head_dim": 128,
    "index_n_heads": 64, "index_topk": 2048, "intermediate_size": 18432,
    "kv_lora_rank": 512, "max_position_embeddings": 163840,
    "model_type": "deepseek_v32", "moe_intermediate_size": 2048,
    "moe_layer_freq": 1, "n_group": 8, "n_routed_experts": 256,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 61, "num_key_value_heads": 128,
    "num_nextn_predict_layers": 1, "q_lora_rank": 1536,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "rope_theta": 10000, "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid", "tie_word_embeddings": False,
    "topk_group": 4, "topk_method": "noaux_tc", "v_head_dim": 128,
    "vocab_size": 129280}
LISTED = ("decode_batch_occupancy", "engine_queue_wait_ms",
          "prefill_device_ms.batch", "decode_device_ms.batch",
          "prefill_useful_share.batch", "moe_router_device_ms",
          "moe_dispatch_combine_device_ms", "moe_experts_device_ms")
UNLISTED = ("dsa_index_decode_ms", "dsa_select_decode_ms",
            "dsa_read_decode_ms", "dsa_index_prefill_ms",
            "dsa_select_prefill_ms", "dsa_read_prefill_ms",
            "dsa_selected_share", "dsa_read_roofline",
            "dsv32_step_hbm_roofline")


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
                    if r["name"] == "DeepSeek-V3.2-Exp"]
        assert row["config"] == PUBLISHED
        assert row["source_url"] == config["source"]
    except FileNotFoundError:          # a checkout without the guides
        pass
    assert config["reduced"] == list(REDUCED)
    assert config["published"] == REDUCED
    assert {k: v for k, v in config.items() if k in PUBLISHED
            and k not in REDUCED} == {
        k: v for k, v in PUBLISHED.items() if k not in REDUCED}
    assert {k: config[k] for k in REDUCED} == {
        "num_hidden_layers": 5, "first_k_dense_replace": 1,
        "n_routed_experts": 16, "vocab_size": 16160,
        "num_nextn_predict_layers": 0}
    # the guide's floors: four layers after the dense one, 8 experts, an
    # eighth of the vocabulary; sixteen shares of the 256
    assert config["vocab_size"] * 8 == PUBLISHED["vocab_size"]
    assert config["expert_share"] == [0, 16]
    assert set(REDUCED) <= set(config["assumed"])
    for key in ("indexer", "selection", "second_pool", "chunked_prefill",
                "routing", "seeded_parameters", "latent_attention"):
        assert len(config["assumed"][key]) > 100, key
    assert "sixteen chips" in config["deployment"]
    assert config["engine"] == {
        "page_size": 16, "max_prompt_len": 16384, "max_new_tokens": 1024,
        "max_batch": 16, "num_pages": 17409, "prefill_chunk": 4096}


def test_the_files_arithmetic_is_what_the_program_makes(config):
    """By shapes alone (``jax.eval_shape``): the tree the engine stores is
    9.27 GB to 1%, the two pools 2.14 GB, a position 7,680 B."""
    import jax
    from ray_tpu.models import llama
    family = spec.load_part("families", config["family"])
    engine = config["engine"]
    model = family.program_config(
        config, engine["max_prompt_len"] + engine["max_new_tokens"])
    assert (model.num_experts, model.expert_share, model.expert_groups) \
        == (256, (0, 16), (8, 4))
    assert (model.index_heads, model.index_head_dim, model.index_topk) \
        == (64, 128, 2048)
    assert llama.llama_prefill_chunks(model)
    stored = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), model))
    weight_bytes = sum(a.size * a.dtype.itemsize
                       for a in jax.tree.leaves(stored))
    assert abs(weight_bytes / 9.27e9 - 1) < 0.01
    # ... and by the family's own count of parameters, in bf16
    assert abs(family.weight_params(config) * 2 / 9.27e9 - 1) < 0.01
    each = family.layer_params(config)
    assert round(each["attention"] / 1e6, 2) == 187.11
    assert round(each["indexer"] / 1e6, 2) == 13.96
    assert round(each["expert"] / 1e6, 2) == 44.04
    assert round(each["dense"] / 1e6, 2) == 396.36
    assert stored["layers"]["mlp"]["wgu"].shape == (4, 16, 2, 7168, 2048)
    assert stored["layers"]["mlp"]["router"].shape == (4, 7168, 256)
    assert stored["lm_head"].shape == (7168, 16160)
    assert stored["dense_layers"]["attn"]["index_wq"].shape \
        == (1, 1536, 64, 128)
    pools = jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"]))
    assert [p.shape for p in pools] == [(5, 17409, 16, 640),
                                        (5, 17409, 16, 128)]
    held = sum(p.size * p.dtype.itemsize for p in pools)
    assert round(held / 1e9, 2) == 2.14
    assert family.kv_bytes_per_token(config) == 7680 \
        == held // (17409 * 16)
    # sixteen sequences of the longest request and the scratch page
    assert engine["num_pages"] == 16 * (16384 + 1024) // 16 + 1
    shape = family.dsa_shape(config)
    assert (shape["row_bytes"], shape["key_bytes"]) == (1280, 256)


def test_the_family_refuses_what_the_program_does_not_run(config):
    family = spec.load_part("families", config["family"])
    for key, value in (("num_nextn_predict_layers", 1),
                       ("scoring_func", "softmax"),
                       ("tie_word_embeddings", True),
                       ("n_routed_experts", 32)):
        with pytest.raises(ValueError):
            family.program_config({**config, key: value}, 64)
    with pytest.raises(ValueError):
        family.program_config({**config, "rope_scaling": None}, 64)


def test_what_the_cell_adds_to_the_lists(bench):
    entry, = [c for c in bench["configs"] if c["name"] == NAME]
    assert entry["file"] == f"benchmark/configs/{NAME}.json"
    assert entry["reduced"] == list(REDUCED)
    assert entry["source"].endswith("DeepSeek-V3.2-Exp/blob/main/config.json")
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) \
        == (NAME, "longctx-mixed", 1)
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
    # one four-chip cell still, of a quarter of the cells at most
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1


def test_the_unlisted_readers_are_files_with_no_entry(bench):
    """(``test_prefill_scopes.py::test_the_readers_are_files_with_no_entry_
    and_the_tool_finds_them`` pins the folder's files as PR 55 left them and
    is marked stale; its other assertions run here, on whatever is there.)"""
    from benchmark.tools import read_profile
    names = {m["name"] for kind in ("end_to_end", "per_layer")
             for m in bench[kind]}
    files = {f[:-3] for f in os.listdir(read_profile.UNLISTED)
             if f.endswith(".py")}
    assert set(UNLISTED) <= files
    assert not files & (names | {n.split(".")[0] for n in names})
    for name in sorted(names | files):
        assert callable(read_profile.reader(name)), name
    for stem in (*UNLISTED, *LISTED):
        assert callable(read_profile.reader(stem.split(".")[0] + ".dsv32"))
    with pytest.raises(SystemExit, match="no reader"):
        read_profile.reader("dsa_nothing_ms")


def test_the_cells_traffic_is_the_issues():
    traffic = spec.load_json("traffic", "longctx-mixed.json")
    assert traffic["generator"] == "closed_loop_serve_longctx"
    assert (traffic["clients"], traffic["block"]) == (20, 20)
    assert traffic["prompt_tokens"] == {"distribution": "uniform",
                                        "min": 4096, "max": 16384}
    assert traffic["output_tokens"] == {"distribution": "uniform",
                                        "min": 256, "max": 1024}
    generator = spec.load_part("generators", traffic["generator"])
    assert callable(generator.run)
