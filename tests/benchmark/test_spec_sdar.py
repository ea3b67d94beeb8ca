"""BENCHMARK.json's SDAR-30B-A3B-Chat configuration against its published
widths, and what its cell adds to the lists. Beside ``test_spec.py`` and
``test_spec_xing.py``, which a PR that brings a configuration may not edit."""

import itertools

import pytest

from benchmark import spec
from test_spec_xing import XING_PUBLISHED

CELL = "serve-sdar-block-decode"


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


# config.json of JetLM/SDAR-30B-A3B-Chat as the model-configs catalog has it:
# every key of it stands in the configuration's file, and only the one that
# ``reduced`` lists differs.
SDAR_PUBLISHED = {
    "attention_bias": False, "decoder_sparse_step": 1, "head_dim": 128,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "max_position_embeddings": 32768, "max_window_layers": 48,
    "mlp_only_layers": [], "model_type": "sdar_moe",
    "moe_intermediate_size": 768, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 128, "num_experts_per_tok": 8,
    "num_hidden_layers": 48, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_scaling": None, "rope_theta": 1000000, "sliding_window": None,
    "tie_word_embeddings": False, "use_sliding_window": False,
    "vocab_size": 151936}


def test_published_widths_of_sdar(bench):
    sdar = spec.load_json("configs", "sdar-30b-a3b-chat-6l.json")
    assert (sdar["hidden_size"], sdar["head_dim"],
            sdar["num_attention_heads"], sdar["num_key_value_heads"],
            sdar["moe_intermediate_size"], sdar["num_experts"],
            sdar["num_experts_per_tok"], sdar["vocab_size"]) == \
        (2048, 128, 32, 4, 768, 128, 8, 151936)
    assert sdar["reduced"] == ["num_hidden_layers"]
    assert sdar["num_hidden_layers"] == 6
    assert sdar["published"] == {"num_hidden_layers": 48}
    assert {k: v for k, v in sdar.items() if k in SDAR_PUBLISHED
            and k != "num_hidden_layers"} == \
        {k: v for k, v in SDAR_PUBLISHED.items() if k != "num_hidden_layers"}
    generation = sdar["assumed"]["generation"]
    assert {k: generation[k] for k in (
        "block_length", "denoising_steps", "remasking",
        "confidence_threshold", "mask_token")} == {
        "block_length": 4, "denoising_steps": 4,
        "remasking": "low_confidence_dynamic", "confidence_threshold": 0.9,
        "mask_token": 151669}
    for key in ("masks", "logits", "qk_norm", "prompt", "seeded_parameters"):
        assert sdar["assumed"][key]
    assert "pipeline stages" in sdar["deployment"]
    engine = sdar["engine"]
    pages_per_sequence = (engine["max_prompt_len"]
                          + engine["max_new_tokens"]) // engine["page_size"]
    assert engine == {"page_size": 16, "max_prompt_len": 512,
                      "max_new_tokens": 1024, "max_batch": 32,
                      "num_pages": 32 * pages_per_sequence + 1}
    assert engine["num_pages"] == 3073
    assert engine["page_size"] % generation["block_length"] == 0
    assert 0 < sdar["numerics"]["logits_rtol"] < 0.05


def test_the_program_takes_the_configuration_at_its_published_widths():
    family = spec.load_part("families", "sdar")
    sdar = spec.load_json("configs", "sdar-30b-a3b-chat-6l.json")
    model = family.program_config(sdar, 1536)
    assert (model.head_dim, model.embed_dim // model.num_heads) == (128, 64)
    assert (model.block_length, model.denoise_steps, model.mask_token,
            model.confidence_threshold) == (4, 4, 151669, 0.9)
    assert model.qk_norm_per_head and not model.qk_norm
    assert (model.num_experts, model.experts_per_token, model.mlp_dim,
            model.norm_topk_prob) == (128, 8, 768, True)
    # the issue's arithmetic: a layer, the table and head, the pages
    layer = 2048 * 32 * 128 * 2 + 2048 * 4 * 128 * 2 + 2048 * 128 \
        + 128 * 3 * 2048 * 768
    assert round(layer / 1e6, 1) == 623.1
    assert family.kv_bytes_per_token(sdar) == 12288
    assert round(3073 * 16 * 12288 / 1e9, 3) == 0.604


def test_what_the_cell_adds_to_the_lists(bench):
    assert len(bench["workloads"]) == 8 and len(bench["configs"]) == 7
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    assert bench["workloads"][-1] == {
        "name": CELL, "config": "sdar-30b-a3b-chat-6l",
        "traffic": "block-decode-batch", "chips": 1,
        "why": bench["workloads"][-1]["why"]}
    assert bench["configs"][-1]["reduced"] == ["num_hidden_layers"]
    served, = [m for m in bench["end_to_end"]
               if m["name"] == "served_tokens_per_s"]
    assert served["workloads"][-1] == CELL and len(served["workloads"]) == 5
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert len(mine) == 20
    assert all(m["name"].endswith(".sdar")
               and m["moves"] == "served_tokens_per_s" for m in mine)
    # no entry that was there lists the cell: new entries only
    assert not [m["name"] for m in bench["per_layer"]
                if CELL in m.get("workloads", []) and m not in mine]
    assert "decode_hbm_roofline.sdar" not in {m["name"] for m in mine}
    for metric in mine:
        spec.metric_reader(metric["name"])       # a reader for each
    layers = {m["layer"] for m in bench["per_layer"] if m not in mine}
    assert {m["layer"] for m in mine} <= layers


def test_the_cells_traffic_is_the_issues():
    """40 callers for 32 slots, prompts uniform 64-512, outputs uniform
    256-1024 (``decode-heavy-batch``'s lengths), in blocks of 40 that the
    run's seed shuffles."""
    from benchmark.generators import closed_loop_serve
    traffic = spec.load_json("traffic", "block-decode-batch.json")
    control = spec.load_json("traffic", "decode-heavy-batch.json")
    assert set(traffic) == set(control)
    assert traffic["generator"] == "closed_loop_serve_blocks"
    assert (traffic["clients"], traffic["block"]) == (40, 40)
    assert traffic["prompt_tokens"] == control["prompt_tokens"] == {
        "distribution": "uniform", "min": 64, "max": 512}
    assert traffic["output_tokens"] == control["output_tokens"] == {
        "distribution": "uniform", "min": 256, "max": 1024}
    block = list(itertools.islice(closed_loop_serve.plan(traffic, 3), 40))
    other = list(itertools.islice(closed_loop_serve.plan(traffic, 7), 40))
    assert block != other                  # the seed orders the lengths
    assert sorted(p for p, _ in block) == sorted(p for p, _ in other)
    engine = spec.load_json("configs", "sdar-30b-a3b-chat-6l.json")["engine"]
    assert max(p for p, _ in block) <= engine["max_prompt_len"]
    assert max(o for _, o in block) <= engine["max_new_tokens"]
    cell = spec.load_cell(spec.load_benchmark(), CELL)
    assert cell["traffic"]["name"] == "block-decode-batch"
    assert cell["config"]["family"] == "sdar"


def test_published_widths_of_xing_still_hold(bench):
    """``test_spec_xing.py::test_published_widths_of_xing`` less its count
    of the benchmark's cells and configurations as PR 34 left them (7 and
    6), which no PR that adds a cell can keep: ``tests/conftest.py`` marks
    that test as expected to fail, and its other assertions run here."""
    xing = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")
    reduced = ["num_hidden_layers", "first_k_dense_replace",
               "num_nextn_predict_layers"]
    assert xing["reduced"] == reduced
    assert {k: xing[k] for k in reduced} == {
        "num_hidden_layers": 6, "first_k_dense_replace": 1,
        "num_nextn_predict_layers": 0}
    assert xing["published"] == {k: XING_PUBLISHED[k] for k in reduced}
    assert {k: v for k, v in xing.items() if k in XING_PUBLISHED
            and k not in reduced} == \
        {k: v for k, v in XING_PUBLISHED.items() if k not in reduced}
    assert xing["engine"]["num_pages"] == 8193
    assert 0 < xing["numerics"]["logits_rtol"] < 0.05
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == ["serve-xing-reasoning-batch"]]
    assert len(mine) == 14 and all(n.endswith(".xing") for n in mine)
