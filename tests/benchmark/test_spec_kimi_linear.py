"""BENCHMARK.json's Kimi-Linear-48B-A3B configuration against its published
widths, the share it holds, and what its cell adds to the lists.  Beside
``test_spec.py`` and the other ``test_spec_*.py``, which a PR that brings a
configuration may not edit.  Pins no count of the benchmark's cells or
configurations, nor the lists' last entries: the next cell would break it."""

import itertools
import json

import pytest

from benchmark import spec

CELL = "serve-kimi-linear-reasoning-wide"
NAME = "kimi-linear-48b-a3b-8l"
REDUCED = ["num_hidden_layers", "linear_attn_config", "num_experts",
           "vocab_size"]
KDA = [n for n in range(1, 27) if n % 4]
# config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct as the model-configs
# catalog has it: every key of it stands in the configuration's file, and only
# those that ``reduced`` lists differ.
KIMI_PUBLISHED = {
    "first_k_dense_replace": 1, "head_dim": 72, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 9216, "kv_lora_rank": 512,
    "linear_attn_config": {
        "full_attn_layers": [4, 8, 12, 16, 20, 24, 27], "head_dim": 128,
        "kda_layers": KDA, "num_heads": 32, "short_conv_kernel_size": 4},
    "mla_use_nope": True, "model_max_length": 1048576,
    "model_type": "kimi_linear", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "moe_renormalize": True,
    "moe_router_activation_func": "sigmoid", "num_attention_heads": 32,
    "num_expert_group": 1, "num_experts": 256, "num_experts_per_token": 8,
    "num_hidden_layers": 27, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 0, "num_shared_experts": 1,
    "q_lora_rank": None, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-05, "rope_scaling": None, "rope_theta": 10000,
    "routed_scaling_factor": 2.446, "tie_word_embeddings": False,
    "topk_group": 1, "use_grouped_topk": True, "v_head_dim": 128,
    "vocab_size": 163840}


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def kimi():
    return spec.load_json("configs", NAME + ".json")


def test_published_widths_of_kimi_linear(kimi):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Kimi-Linear-48B-A3B-Instruct"]
        assert row["config"] == KIMI_PUBLISHED
        assert row["source_url"] == kimi["source"]
    except FileNotFoundError:          # a checkout without the guides
        pass
    assert kimi["reduced"] == REDUCED
    assert kimi["published"] == {k: KIMI_PUBLISHED[k] for k in REDUCED}
    assert {k: v for k, v in kimi.items() if k in KIMI_PUBLISHED
            and k not in REDUCED} == \
        {k: v for k, v in KIMI_PUBLISHED.items() if k not in REDUCED}
    # the cut: two whole periods, a quarter of the experts and of the ids
    assert kimi["num_hidden_layers"] == 8
    assert kimi["num_experts"] * 4 == KIMI_PUBLISHED["num_experts"]
    assert kimi["vocab_size"] * 4 == KIMI_PUBLISHED["vocab_size"]
    assert kimi["expert_share"] == [0, 4]
    linear, published = (kimi["linear_attn_config"],
                         KIMI_PUBLISHED["linear_attn_config"])
    assert linear["kda_layers"] == [1, 2, 3, 5, 6, 7] == \
        [n for n in published["kda_layers"] if n <= 8]
    assert linear["full_attn_layers"] == [4, 8] == \
        [n for n in published["full_attn_layers"] if n <= 8]
    # no width inside the listed group changed
    assert {k: v for k, v in linear.items() if not k.endswith("_layers")} \
        == {k: v for k, v in published.items() if not k.endswith("_layers")}
    # the guide's floors: a whole period and four layers behind the dense
    # one, 8 routed experts, an eighth of the vocabulary
    assert kimi["num_hidden_layers"] - kimi["first_k_dense_replace"] >= 4
    assert kimi["num_experts"] >= 8
    assert kimi["vocab_size"] * 8 >= KIMI_PUBLISHED["vocab_size"]
    for key in ("num_hidden_layers", "linear_attn_config", "num_experts",
                "vocab_size", "kda_gate_rank", "gate_parameters",
                "gate_precision", "positions", "routing", "fused_projections",
                "layer_norms", "biases", "weights", "memory"):
        assert kimi["assumed"][key], key
    assert kimi["assumed_sizes"] == {"kda_gate_rank": 128}
    assert "four chips share each layer" in kimi["deployment"]
    assert "pipeline" in kimi["deployment"]
    engine = kimi["engine"]
    pages_per_sequence = (engine["max_prompt_len"]
                          + engine["max_new_tokens"]) // engine["page_size"]
    assert engine == {"page_size": 16, "max_prompt_len": 1024,
                      "max_new_tokens": 3072, "max_batch": 64,
                      "num_pages": 64 * pages_per_sequence + 1}
    assert engine["num_pages"] == 16385
    assert kimi["max_concurrent_queries"] == 128
    assert 0 < kimi["numerics"]["logits_rtol"] < 1
    assert "NOT SEEN" in kimi["numerics"]["why"]


def test_the_cut_is_a_chips_share_and_its_bytes_are_the_files(kimi):
    import jax
    from ray_tpu.models import llama
    family = spec.load_part("families", "kimi_linear")
    model = family.program_config(kimi, 4096)
    assert family.pattern(kimi) == ("linear", "linear", "linear", "full")
    assert (model.num_experts, model.expert_share) == (256, (0, 4))
    stored = jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), model))
    groups = stored["layers"]
    assert len(groups) == 8
    assert ["linear" in g for g in groups] == [True, True, True, False] * 2
    # the router keeps its published 256 outputs over the 64 experts held
    for group in groups[1:]:
        assert group["mlp"]["router"].shape == (1, 2304, 256)
        assert group["mlp"]["router"].dtype.name == "float32"
        assert group["mlp"]["wgu"].shape == (1, 64, 2, 2304, 1024)
        assert group["mlp"]["wgu"].dtype.name == "bfloat16"
        assert group["shared"]["wgu"].shape == (1, 2, 2304, 1024)
    assert groups[0]["mlp"]["wgu"].shape == (1, 2, 2304, 9216)
    assert stored["lm_head"].shape == (2304, 40960)
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(stored))
    each = family.layer_params(kimi)
    counted = 6 * each["linear"] + 2 * each["full"] + 8 * each["norms"] \
        + each["dense"] + 7 * (each["shared"] + each["router"]
                               + 64 * each["expert"]) \
        + 2 * 2304 * 40960 + 2304
    assert sum(a.size for a in jax.tree.leaves(stored)) == counted
    assert round(counted / 1e6) == 3772               # the issue's 3,772M
    assert abs(weights / 7.55e9 - 1) < 0.01           # bf16, the file's
    engine = kimi["engine"]
    kp, vp = jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"]))
    assert vp.v_pages is None
    # a latent page's rows are stored in whole 128-lane tiles: 640 for 576
    assert kp.shape == (2, 16385, 16, 640)
    assert kp.size * 2 * 576 // 640 == \
        16385 * 16 * family.kv_bytes_per_token(kimi)
    assert vp.state.size * 4 == 64 * family.state_bytes_per_slot(kimi)
    assert abs(vp.state.size * 4 / 0.805e9 - 1) < 0.01
    held = weights + kp.size * 2 + vp.state.size * 4 + vp.conv.size * 2
    assert 0.55 < held / 16e9 < 0.60                  # 9.05 GB of 16


def test_a_configuration_the_family_cannot_run_is_refused(kimi):
    family = spec.load_part("families", "kimi_linear")
    linear = kimi["linear_attn_config"]
    for change, message in (
            ({"linear_attn_config": {**linear, "kda_layers": [1, 2, 3, 5, 6],
                                     "full_attn_layers": [4, 7, 8]}},
             "whole periods"),
            ({"linear_attn_config": {**linear, "kda_layers": [1, 2, 3]}},
             "every layer"),
            ({"num_experts": 32}, "experts held here"),
            ({"q_lora_rank": 1536}, "q_lora_rank"),
            ({"mla_use_nope": False}, "mla_use_nope"),
            ({"moe_router_activation_func": "softmax"},
             "moe_router_activation_func"),
            ({"num_expert_group": 8}, "num_expert_group")):
        with pytest.raises(ValueError, match=message):
            family.program_config({**kimi, **change}, 4096)


def test_what_the_cell_adds_to_the_lists(bench):
    cell, = [w for w in bench["workloads"] if w["name"] == CELL]
    assert cell == {"name": CELL, "config": NAME,
                    "traffic": "reasoning-long-wide", "chips": 1,
                    "why": cell["why"]}
    assert len(cell["why"]) <= 200
    # nothing it measures exists only across chips: the four-chip cells are
    # the ones that were there
    assert [w["name"] for w in bench["workloads"] if w["chips"] == 4] == \
        ["train-gpt2-large-fsdp2tp2"]
    config, = [c for c in bench["configs"] if c["name"] == NAME]
    assert config["reduced"] == REDUCED
    assert config["file"] == f"benchmark/configs/{NAME}.json"
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert CELL in served["workloads"]
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert all(m["name"].endswith(".kimi")
               and m["moves"] == "served_tokens_per_s"
               and set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"} for m in mine)
    names = [m["name"][:-len(".kimi")] for m in mine]
    assert names == [
        "decode_device_ms", "decode_batch_occupancy", "host_loop_cpu_ms",
        "host_loop_busy_share", "linear_state_device_ms",
        "kda_gate_device_ms", "linear_state_roofline", "latent_kv_device_ms",
        "latent_read_roofline", "moe_experts_device_ms",
        "moe_routed_roofline", "moe_routed_hit_share",
        "moe_kept_assignment_share", "kimi_step_hbm_roofline"]
    # the contract admits 128 per-layer entries and the benchmark had 114:
    # of ISSUE 51's 24 these 14 are listed; the readers of the other ten
    # (found by stem) read the same traced run when asked (PERF.md)
    assert len(bench["per_layer"]) <= 128
    for stem in ("decode_ahead_share", "gc_pause_share",
                 "linear_conv_device_ms", "linear_gate_norm_device_ms",
                 "paged_read_useful_share", "paged_kernel_share",
                 "moe_router_device_ms", "moe_dispatch_combine_device_ms",
                 "moe_shared_device_ms", "moe_routed_load_max_over_mean"):
        spec.metric_reader(stem + ".kimi")
    # the entries lie together, in the order they were appended
    at = bench["per_layer"].index(mine[0])
    assert bench["per_layer"][at:at + len(mine)] == mine
    # a reader that finds nothing in SOME traced run may not be listed: no
    # prefill is sure to fall inside the traced seconds 3-8
    assert not set(names) & {"prefill_device_ms", "kda_scan_roofline"}
    # no entry that was there lists the cell: new entries only
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", []) and m not in mine]
    for metric in mine:
        spec.metric_reader(metric["name"])       # a reader for each
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%" and metric["better"] == "higher"
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    assert {m["layer"] for m in mine} <= layers
    # the two readers that count THIS model's layers are its own files
    assert spec.metric_reader("latent_read_roofline.kimi").__module__ \
        .endswith("latent_read_roofline_kimi")
    assert spec.metric_reader("linear_state_roofline.kimi").__module__ \
        .endswith("linear_state_roofline_kimi")


def test_what_the_hybrid_cell_added_still_stands(bench):
    """The assertions of the snapshots this PR's cell makes stale that are
    not counts of the benchmark's cells (9) and configurations (8)
    (``tests/conftest.py::STALE_SNAPSHOTS``): the hybrid cell, its
    configuration and its place among ``served_tokens_per_s``'s cells."""
    hybrid = "serve-olmo-hybrid-decode-wide"
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
    assert served["workloads"][6:] == [CELL]
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


def test_the_paged_read_entries_still_stand(bench):
    """``test_paged_kernel_share_xing.py::
    test_the_benchmark_lists_it_last_and_the_others_before_it`` less the
    per-layer list's last five names as PR 50 left them (marked as expected
    to fail): the five lie together, in that order, ahead of this PR's."""
    import test_paged_kernel_share_xing as xing
    import test_paged_read_metrics as reads
    listed = bench["per_layer"]
    entries = {m["name"]: m for m in listed}
    five = ["paged_read_roofline.hybrid", "paged_read_roofline.ouro",
            "paged_kernel_share.hybrid", "paged_kernel_share", xing.NAME]
    at = [m["name"] for m in listed].index(five[0])
    assert [m["name"] for m in listed[at:at + 5]] == five
    assert listed[at + 5]["workloads"] == [CELL]
    shares = {**reads.SHARES, xing.NAME: (xing.CELL, "served_tokens_per_s")}
    for name, (cell, moves) in shares.items():
        entry = entries[name]
        assert entry["workloads"] == [cell] and entry["moves"] == moves
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"]) == ("%", "higher", "program_counter",
                                    "ops kernels")
        assert set(entry) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"}
    for name, cell in reads.ROOFLINES.items():
        entry = entries[name]
        assert entry["workloads"] == [cell]
        assert (entry["unit"], entry["better"], entry["source"],
                entry["layer"], entry["moves"]) == (
            "%", "higher", "device_trace", "ops kernels",
            "served_tokens_per_s")


def test_the_cells_traffic_is_the_issues(kimi):
    """80 callers for 64 slots, prompts uniform 256-1024, answers uniform
    1024-3072 (``reasoning-long-batch``'s lengths at twice its width), in
    blocks of 80 that the run's seed shuffles, ids from the slice."""
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "reasoning-long-wide.json")
    control = spec.load_json("traffic", "reasoning-long-batch.json")
    assert set(traffic) == set(control)
    assert traffic["generator"] == "closed_loop_serve_checked"
    assert (traffic["clients"], traffic["block"]) == (80, 80) == \
        (2 * control["clients"], 2 * control["block"])
    assert traffic["prompt_tokens"] == control["prompt_tokens"] == {
        "distribution": "uniform", "min": 256, "max": 1024}
    assert traffic["output_tokens"] == control["output_tokens"] == {
        "distribution": "uniform", "min": 1024, "max": 3072}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 80))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 80))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    engine = kimi["engine"]
    assert max(p for p, _ in block) <= engine["max_prompt_len"]
    assert max(o for _, o in block) <= engine["max_new_tokens"]
    assert traffic["clients"] > engine["max_batch"]       # never starves
    assert kimi["max_concurrent_queries"] >= traffic["clients"]
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    assert cell["traffic"]["name"] == "reasoning-long-wide"
    assert cell["config"]["family"] == "kimi_linear"
    assert cell["config"]["vocab_size"] == 40960     # the ids' slice
