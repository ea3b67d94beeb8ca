"""BENCHMARK.json's Olmo-Hybrid-7B configuration against its published
widths, and what its cell adds to the lists.  Beside ``test_spec.py``,
``test_spec_xing.py`` and ``test_spec_sdar.py``, which a PR that brings a
configuration may not edit."""

import itertools
import json

import pytest

from benchmark import spec
from test_spec_sdar import SDAR_PUBLISHED

CELL = "serve-olmo-hybrid-decode-wide"
PERIOD = ["linear_attention"] * 3 + ["full_attention"]
# config.json of allenai/Olmo-Hybrid-7B as the model-configs catalog has it:
# every key of it stands in the configuration's file, and only those that
# ``reduced`` lists differ.
HYBRID_PUBLISHED = {
    "model_type": "olmo_hybrid", "vocab_size": 100352, "hidden_size": 3840,
    "intermediate_size": 11008, "num_hidden_layers": 32,
    "num_attention_heads": 30, "num_key_value_heads": 30,
    "hidden_act": "silu", "max_position_embeddings": 65536,
    "attention_bias": False, "rms_norm_eps": 1e-06,
    "tie_word_embeddings": False, "layer_types": PERIOD * 8,
    "linear_num_key_heads": 30, "linear_num_value_heads": 30,
    "linear_key_head_dim": 96, "linear_value_head_dim": 192,
    "linear_conv_kernel_dim": 4, "linear_allow_neg_eigval": True,
    "rope_parameters": {"rope_theta": None}}
REDUCED = ["num_hidden_layers", "layer_types"]


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


@pytest.fixture(scope="module")
def hybrid():
    return spec.load_json("configs", "olmo-hybrid-7b-12l.json")


def test_published_widths_of_olmo_hybrid(hybrid):
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    try:
        with open(catalog) as f:
            row, = [r for r in map(json.loads, f)
                    if r["name"] == "Olmo-Hybrid-7B"]
        assert row["config"] == HYBRID_PUBLISHED
        assert row["source_url"] == hybrid["source"]
    except FileNotFoundError:          # a checkout without the guides
        pass
    assert hybrid["reduced"] == REDUCED
    assert hybrid["published"] == {k: HYBRID_PUBLISHED[k] for k in REDUCED}
    assert {k: v for k, v in hybrid.items() if k in HYBRID_PUBLISHED
            and k not in REDUCED} == \
        {k: v for k, v in HYBRID_PUBLISHED.items() if k not in REDUCED}
    assert hybrid["num_hidden_layers"] == 12
    for key in ("num_hidden_layers", "layer_types", "layer_norms", "qk_norm",
                "positions", "head_dim", "linear_layer", "fused_projections",
                "gate_parameters", "biases", "weights", "state_layout"):
        assert hybrid["assumed"][key]
    assert "pipeline stages" in hybrid["deployment"]
    engine = hybrid["engine"]
    pages_per_sequence = (engine["max_prompt_len"]
                          + engine["max_new_tokens"]) // engine["page_size"]
    assert engine == {"page_size": 16, "max_prompt_len": 512,
                      "max_new_tokens": 1024, "max_batch": 48,
                      "num_pages": 48 * pages_per_sequence + 1}
    assert engine["num_pages"] == 4609
    assert 0 < hybrid["numerics"]["logits_rtol"] < 1
    assert "state in bfloat16" in hybrid["numerics"]["why"]


def test_the_cut_keeps_whole_periods_and_its_bytes_are_the_files(hybrid):
    """In the share test's place (no experts or vocabulary are sliced): the
    12 layers are the published first twelve, three whole periods, and what
    the engine would hold equals the file's arithmetic to 1%."""
    import jax
    from ray_tpu.models import llama
    assert hybrid["layer_types"] == HYBRID_PUBLISHED["layer_types"][:12] \
        == PERIOD * 3
    family = spec.load_part("families", "olmo_hybrid")
    model = family.program_config(hybrid, 1536)
    assert model.layer_pattern == ("linear", "linear", "linear", "full")
    assert (model.linear_heads, model.linear_key_dim, model.linear_value_dim,
            model.linear_conv, model.linear_neg_eigval) == (30, 96, 192, 4,
                                                            True)
    assert (model.head_dim, model.qk_norm, model.pre_norm, model.post_norm,
            model.rope_theta) == (128, True, False, True, 0.0)
    stored = jax.eval_shape(
        lambda: family.init(jax.random.PRNGKey(0), model))
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(stored))
    each = family.layer_params(hybrid)
    counted = 9 * each["linear"] + 3 * each["full"] \
        + 2 * 3840 * 100352 + 3840
    assert sum(a.size for a in jax.tree.leaves(stored)) == counted
    assert abs(weights / 6.54e9 - 1) < 0.01          # bf16, the file's
    engine = hybrid["engine"]
    kp, vp = jax.eval_shape(lambda: llama.llama_init_paged_cache(
        model, engine["num_pages"], engine["page_size"], None,
        engine["max_batch"]))
    pages = 2 * kp.size * kp.dtype.itemsize
    assert pages == 4609 * 16 * family.kv_bytes_per_token(hybrid)
    assert abs(pages / 3.40e9 - 1) < 0.01
    assert vp.state.size * 4 == 48 * family.state_bytes_per_slot(hybrid)
    assert abs(vp.state.size * 4 / 0.955e9 - 1) < 0.01
    assert abs(vp.conv.size * 2 / 0.030e9 - 1) < 0.01
    assert abs((weights + pages + vp.state.size * 4 + vp.conv.size * 2)
               / 10.9e9 - 1) < 0.01


def test_a_list_that_is_not_whole_periods_is_refused(hybrid):
    family = spec.load_part("families", "olmo_hybrid")
    for kinds, message in (
            (PERIOD * 2 + ["linear_attention"] * 4, "whole periods"),
            (["sliding_attention"] * 12, "layer_types of"),
            (PERIOD * 2, "num_hidden_layers long")):
        with pytest.raises(ValueError, match=message):
            family.program_config({**hybrid, "layer_types": kinds}, 1536)
    with pytest.raises(ValueError, match="rope_parameters"):
        family.program_config(
            {**hybrid, "rope_parameters": {"rope_theta": 500000}}, 1536)
    with pytest.raises(ValueError, match="key head for every value head"):
        family.program_config({**hybrid, "linear_num_key_heads": 15}, 1536)


def test_what_the_cell_adds_to_the_lists(bench):
    assert len(bench["workloads"]) == 9 and len(bench["configs"]) == 8
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "olmo-hybrid-7b-12l",
        "traffic": "decode-heavy-wide", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert bench["configs"][-1]["reduced"] == REDUCED
    assert bench["configs"][-1]["file"] == \
        "benchmark/configs/olmo-hybrid-7b-12l.json"
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert served["workloads"][-1] == CELL and len(served["workloads"]) == 6
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert bench["per_layer"][-len(mine):] == mine       # appended, last
    assert all(m["name"].endswith(".hybrid")
               and m["moves"] == "served_tokens_per_s"
               and set(m) == {"name", "unit", "better", "source", "layer",
                              "moves", "workloads"} for m in mine)
    names = {m["name"][:-len(".hybrid")] for m in mine}
    assert {"decode_device_ms", "decode_batch_occupancy",
            "paged_kv_device_ms", "paged_read_useful_share",
            "decode_ahead_share", "host_loop_cpu_ms", "host_loop_busy_share",
            "gc_pause_share", "linear_state_device_ms",
            "linear_conv_device_ms", "linear_gate_norm_device_ms",
            "linear_state_roofline", "hybrid_step_hbm_roofline"} <= names
    # a reader that finds nothing in SOME traced run may not be listed: no
    # prefill falls inside the traced seconds 3-8 (the first answer ends
    # after ~8 s), so nothing that reads a prefill is listed (the scan's
    # cost function, ``costs_linear.chunked_scan``, waits for a reader)
    assert not names & {"prefill_device_ms", "linear_scan_roofline"}
    assert len(mine) == 13
    # no entry that was there lists the cell: new entries only
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", []) and m not in mine]
    for metric in mine:
        spec.metric_reader(metric["name"])       # a reader for each
        if "roofline" in metric["name"]:
            assert metric["unit"] == "%" and metric["better"] == "higher"
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    assert {m["layer"] for m in mine} <= layers


def test_what_the_sdar_cell_added_still_stands(bench):
    """``test_spec_sdar.py::test_what_the_cell_adds_to_the_lists`` less its
    count of the benchmark's cells and configurations as PR 40 left them (8
    and 7) and its place at the lists' ends, which no PR that adds a cell
    can keep: ``tests/conftest.py`` marks that test as expected to fail, and
    its other assertions run here."""
    sdar = "serve-sdar-block-decode"
    cell, = [w for w in bench["workloads"] if w["name"] == sdar]
    assert cell == {"name": sdar, "config": "sdar-30b-a3b-chat-6l",
                    "traffic": "block-decode-batch", "chips": 1,
                    "why": cell["why"]}
    config, = [c for c in bench["configs"]
               if c["name"] == "sdar-30b-a3b-chat-6l"]
    assert config["reduced"] == ["num_hidden_layers"]
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert served["workloads"][:5][-1] == sdar
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [sdar]]
    assert len(mine) == 20
    assert all(m["name"].endswith(".sdar")
               and m["moves"] == "served_tokens_per_s" for m in mine)
    assert not [m["name"] for m in bench["per_layer"]
                if sdar in m.get("workloads", []) and m not in mine]
    assert "decode_hbm_roofline.sdar" not in {m["name"] for m in mine}
    for metric in mine:
        spec.metric_reader(metric["name"])
    assert SDAR_PUBLISHED["num_hidden_layers"] == 48


def test_the_cells_traffic_is_the_issues(hybrid):
    """60 callers for 48 slots, prompts uniform 64-512, outputs uniform
    256-1024 (``decode-heavy-batch``'s lengths), in blocks of 60 that the
    run's seed shuffles."""
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "decode-heavy-wide.json")
    control = spec.load_json("traffic", "decode-heavy-batch.json")
    assert set(traffic) == set(control)
    assert traffic["generator"] == "closed_loop_serve_checked"
    assert (traffic["clients"], traffic["block"]) == (60, 60)
    assert traffic["prompt_tokens"] == control["prompt_tokens"] == {
        "distribution": "uniform", "min": 64, "max": 512}
    assert traffic["output_tokens"] == control["output_tokens"] == {
        "distribution": "uniform", "min": 256, "max": 1024}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 60))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 60))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    engine = hybrid["engine"]
    assert max(p for p, _ in block) <= engine["max_prompt_len"]
    assert max(o for _, o in block) <= engine["max_new_tokens"]
    assert traffic["clients"] > engine["max_batch"]       # never starves
    assert hybrid["max_concurrent_queries"] >= traffic["clients"]
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    assert cell["traffic"]["name"] == "decode-heavy-wide"
    assert cell["config"]["family"] == "olmo_hybrid"
