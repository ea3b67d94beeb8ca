"""BENCHMARK.json's LFM2-24B-A2B configuration against its published widths,
the file's arithmetic against what the program makes, and what its cell adds
to the lists.  Beside ``test_spec.py`` and the other ``test_spec_*.py``, which
a PR that brings a configuration may not edit.  Pins no count of the
benchmark's cells or configurations, nor the lists' last entries: the next
cell would break it."""

import itertools
import json

import pytest

from benchmark import spec

CELL = "serve-lfm2-longprompt-wide"
NAME = "lfm2-24b-a2b-9l"
REDUCED = ["num_hidden_layers", "layer_types", "num_dense_layers"]
FORTY = ["conv", "conv", "full_attention"] \
    + ["conv", "conv", "conv", "full_attention"] * 9 + ["conv"]
NINE = ["conv", "full_attention", "conv", "conv", "conv", "full_attention",
        "conv", "conv", "conv"]
# config.json of LiquidAI/LFM2-24B-A2B as the model-configs catalog has it:
# every key of it stands in the configuration's file, and only those that
# ``reduced`` lists differ.
LFM2_PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "layer_types": FORTY,
    "max_position_embeddings": 128000, "model_type": "lfm2_moe",
    "moe_intermediate_size": 1536, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_dense_layers": 2, "num_experts": 64,
    "num_experts_per_tok": 4, "num_hidden_layers": 40,
    "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def lfm2():
    return spec.load_json("configs", NAME + ".json")


def test_published_widths_of_lfm2(lfm2):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "LFM2-24B-A2B"]
        assert row["config"] == LFM2_PUBLISHED
        assert row["source_url"] == lfm2["source"]
    except FileNotFoundError:          # a checkout without the guides
        pass
    assert len(FORTY) == 40 and FORTY.count("full_attention") == 10
    assert lfm2["reduced"] == REDUCED
    assert lfm2["published"] == {k: LFM2_PUBLISHED[k] for k in REDUCED}
    assert {k: v for k, v in lfm2.items() if k in LFM2_PUBLISHED
            and k not in REDUCED} == \
        {k: v for k, v in LFM2_PUBLISHED.items() if k not in REDUCED}
    # the cut: published layer 1 (the leading dense ones count once) and
    # layers 2-9, two whole periods in the published order
    assert lfm2["num_hidden_layers"] == 9 and lfm2["num_dense_layers"] == 1
    assert lfm2["layer_types"] == NINE == FORTY[1:10]
    # the eight expert layers keep the published 3:1
    assert NINE[1:].count("conv") == 3 * NINE[1:].count("full_attention")
    # the guide's floors: a whole period and four layers behind the dense
    # one, 8 routed experts, the whole vocabulary
    assert lfm2["num_hidden_layers"] - lfm2["num_dense_layers"] >= 4
    assert lfm2["num_experts"] >= 8
    for key in (*REDUCED, "tie_word_embeddings", "router", "rotation",
                "positions", "routing", "convolution", "head_dim", "biases",
                "weights", "memory"):
        assert lfm2["assumed"][key], key
    assert lfm2["assumed_sizes"] == {"head_dim": 64}
    assert lfm2["hidden_size"] == \
        lfm2["num_attention_heads"] * lfm2["assumed_sizes"]["head_dim"]
    assert "every layer whole on a chip" in lfm2["deployment"]
    assert "pipeline" in lfm2["deployment"]
    engine = lfm2["engine"]
    pages_per_sequence = (engine["max_prompt_len"]
                          + engine["max_new_tokens"]) // engine["page_size"]
    assert engine == {"page_size": 16, "max_prompt_len": 4096,
                      "max_new_tokens": 64, "max_batch": 48,
                      "num_pages": 48 * pages_per_sequence + 1}
    assert engine["num_pages"] == 12481
    assert lfm2["max_concurrent_queries"] == 128
    assert 0 < lfm2["numerics"]["logits_rtol"] < 0.05
    assert "NOT SEEN" in lfm2["numerics"]["why"]


def test_the_files_arithmetic_is_what_the_program_makes(lfm2):
    import jax
    from ray_tpu.models import llama
    family = spec.load_part("families", "lfm2_moe")
    model = family.program_config(lfm2, 4160)
    assert family.pattern(lfm2) == (
        "conv", "full", "conv", "conv", "conv", "full", "conv", "conv",
        "conv") == model.layer_pattern
    assert (model.head_dim, model.num_heads, model.num_kv_heads) == \
        (64, 32, 8)
    assert (model.num_experts, model.experts_per_token, model.mlp_dim,
            model.dense_mlp_dim, model.first_dense_layers,
            model.shared_experts) == (64, 4, 1536, 11776, 1, 0)
    assert (model.router_scoring, model.router_bias, model.norm_topk_prob,
            model.router_norm_eps, model.routed_scaling) == \
        ("sigmoid", True, True, 1e-6, 1.0)
    assert model.tie_embeddings and model.qk_norm_per_head
    assert (model.linear_conv, model.rope_theta, model.rms_eps) == \
        (3, 1e6, 1e-5)
    stored = jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), model))
    assert "lm_head" not in stored
    groups = stored["layers"]
    assert ["conv" in g for g in groups] == [k == "conv" for k in NINE]
    assert groups[0]["mlp"]["wgu"].shape == (1, 2, 2048, 11776)
    for group in groups[1:]:
        assert group["mlp"]["wgu"].shape == (1, 64, 2, 2048, 1536)
        assert group["mlp"]["wgu"].dtype.name == "bfloat16"
        assert group["mlp"]["router"].shape == (1, 2048, 64)
        assert group["mlp"]["router"].dtype.name == "float32"
        assert "shared" not in group
    assert groups[0]["conv"]["win"].shape == (1, 2048, 6144)
    assert groups[0]["conv"]["taps"].shape == (1, 3, 2048)
    assert groups[1]["attn"]["wkv"].shape == (1, 2048, 2, 8, 64)
    counted = sum(a.size for a in jax.tree.leaves(stored))
    assert counted == family.weight_params(lfm2)
    assert round(counted / 1e6) == 5178               # the issue's 5,178M
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(stored))
    assert abs(weights / 10.36e9 - 1) < 0.01
    each = family.layer_params(lfm2)
    assert round(each["conv"] / 1e6, 2) == 16.78
    assert round((each["full"] - 128) / 1e6, 2) == 10.49
    assert round(each["expert"] / 1e6, 3) == 9.437
    engine = lfm2["engine"]
    kp, vp = jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"]))
    assert kp.shape == vp.v_pages.shape == (2, 12481, 16, 512)
    assert vp.state is None and vp.conv.shape == (7, 48, 2 * 2048)
    assert family.kv_bytes_per_token(lfm2) == 4096
    assert 2 * kp.size * 2 == 12481 * 16 * family.kv_bytes_per_token(lfm2)
    assert vp.conv.size * 2 == 48 * family.tail_bytes_per_slot(lfm2)
    assert family.tail_bytes_per_slot(lfm2) == 57344      # 57 KB a slot
    held = weights + 2 * kp.size * 2 + vp.conv.size * 2
    assert 0.68 < held / 16e9 < 0.72                  # 11.2 GB of 16
    assert family.conv_shape(lfm2) == {"layers": 7, "hidden": 2048,
                                       "taps": 3}
    assert family.moe_shape(lfm2) == {"layers": 8, "experts": 64,
                                      "hidden": 2048, "width": 1536}
    # a decode step with every expert touched reads every parameter once
    assert family.decode_weight_params(lfm2, 8 * 64) == counted


def test_the_family_refuses_what_the_program_does_not_run(lfm2):
    family = spec.load_part("families", "lfm2_moe")
    for change, message in (
            ({"conv_bias": True}, "conv_bias"),
            ({"use_expert_bias": False}, "use_expert_bias"),
            ({"tie_word_embeddings": False}, "tie_word_embeddings"),
            ({"rope_parameters": {"rope_theta": 1e6, "rope_type": "yarn"}},
             "default tables"),
            ({"num_dense_layers": 0}, "num_dense_layers"),
            ({"layer_types": NINE[:8]}, "every layer once"),
            ({"layer_types": ["window"] + NINE[1:]}, "every layer once")):
        with pytest.raises(ValueError, match=message):
            family.program_config({**lfm2, **change}, 4160)


def test_what_the_cell_adds_to_the_lists(bench):
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": NAME,
                    "traffic": "longprompt-wide", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    # nothing it measures exists only across chips
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == \
        ["train-gpt2-large-fsdp2tp2"]
    config, = [c for c in bench["configs"] if c["name"] == NAME]
    assert config["reduced"] == REDUCED
    assert config["file"] == f"benchmark/configs/{NAME}.json"
    assert len(config["why"]) <= 200
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert CELL in served["workloads"]
    # ``per_layer`` stood at the contract's 128 before this cell: it adds NO
    # entry and is appended to nine that were there, whose readers find
    # something to read in every traced run of it and which move the cell's
    # end-to-end metric; the issue's ``.lfm2`` names are read by
    # ``benchmark/tools/read_profile.py`` (test_prefill_scopes.py)
    assert len(bench["per_layer"]) <= 128
    listed = [m for m in bench["per_layer"] if CELL in m.get("workloads", [])]
    assert {m["name"] for m in listed} >= {
        "decode_batch_occupancy", "prefill_device_ms.batch",
        "decode_device_ms.batch", "prefill_useful_share.batch",
        "engine_queue_wait_ms", "paged_kv_device_ms.olmoe",
        "moe_router_device_ms", "moe_dispatch_combine_device_ms",
        "moe_experts_device_ms"}
    for metric in listed:
        assert metric["moves"] == "served_tokens_per_s"
        # appended behind the cell the entry was made for (and whatever a
        # later PR appends behind it)
        assert metric["workloads"].index(CELL) >= 1
        assert callable(spec.metric_reader(metric["name"]))
    assert not [m for m in bench["per_layer"] if m["name"].endswith(".lfm2")]


def test_what_the_hybrid_cell_added_still_stands(bench):
    """``test_spec_kimi_linear.py::test_what_the_hybrid_cell_added_still_
    stands`` less its reading of ``served_tokens_per_s``'s cells past the
    hybrid's as PR 51 left them (marked as expected to fail in
    ``tests/conftest.py::STALE_SNAPSHOTS``): the hybrid cell, its
    configuration, its entries, and the order of the cells that followed."""
    hybrid, kimi = ("serve-olmo-hybrid-decode-wide",
                    "serve-kimi-linear-reasoning-wide")
    cell, = [w for w in bench["workloads"] if w["name"] == hybrid]
    assert cell == {"name": hybrid, "config": "olmo-hybrid-7b-12l",
                    "traffic": "decode-heavy-wide", "chips": 1,
                    "why": cell["why"]}
    config, = [c for c in bench["configs"]
               if c["name"] == "olmo-hybrid-7b-12l"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"]
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert served["workloads"][:6][-1] == hybrid
    assert served["workloads"][6:8] == [kimi, CELL]
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [hybrid]]
    assert all(m["name"].endswith(".hybrid")
               and m["moves"] == "served_tokens_per_s"
               and set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"} for m in mine)
    names = [m["name"][:-len(".hybrid")] for m in mine]
    assert len(names) == 15 and names[-2:] == ["paged_read_roofline",
                                               "paged_kernel_share"]
    assert not set(names) & {"prefill_device_ms", "linear_scan_roofline"}
    assert not [m["name"] for m in bench["per_layer"]
                if hybrid in m.get("workloads", []) and m not in mine]
    for metric in mine:
        spec.metric_reader(metric["name"])
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%" and metric["better"] == "higher"


def test_the_cells_traffic_is_the_issues(lfm2):
    """64 callers for 48 slots, prompts uniform 1024-4096, answers uniform
    16-64 (``longprompt-batch``'s answers, its prompts stretched to twice
    their top, at three times its slots... of which the engine's are 48), in
    blocks of 64 that the run's seed shuffles, ids over the whole
    vocabulary."""
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "longprompt-wide.json")
    control = spec.load_json("traffic", "longprompt-batch.json")
    assert set(traffic) == set(control)
    assert traffic["generator"] == "closed_loop_serve_checked"
    assert (traffic["clients"], traffic["block"]) == (64, 64)
    assert traffic["prompt_tokens"] == {
        "distribution": "uniform", "min": 1024, "max": 4096}
    assert traffic["prompt_tokens"]["min"] == control["prompt_tokens"]["min"]
    assert traffic["prompt_tokens"]["max"] == \
        2 * control["prompt_tokens"]["max"]
    assert traffic["output_tokens"] == control["output_tokens"] == {
        "distribution": "uniform", "min": 16, "max": 64}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 64))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 64))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    engine = lfm2["engine"]
    assert max(p for p, _ in block) <= engine["max_prompt_len"]
    assert max(o for _, o in block) <= engine["max_new_tokens"]
    # a third of the prompts land on the 2,048 rung, two thirds on 4,096
    from ray_tpu.serve.engine.engine import prefill_rungs, rung_for
    rungs = prefill_rungs(engine["max_prompt_len"], engine["page_size"])
    assert list(rungs[-2:]) == [2048, 4096]
    landed = [rung_for(rungs, p) for p, _ in block]
    assert set(landed) == {2048, 4096}
    assert 0.30 < landed.count(2048) / 64 < 0.37
    useful = sum(p for p, _ in block) / sum(landed)
    assert 0.72 < useful < 0.78
    assert traffic["clients"] > engine["max_batch"]       # never starves
    assert lfm2["max_concurrent_queries"] >= traffic["clients"]
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    assert cell["traffic"]["name"] == "longprompt-wide"
    assert cell["config"]["family"] == "lfm2_moe"
    assert cell["config"]["vocab_size"] == 65536
