"""BENCHMARK.json's Xing4.0-29B-A4B configuration against its published
widths, and what its cell adds to the lists. Beside ``test_spec.py``, which a
PR that brings a configuration may not edit."""

import pytest

from benchmark import spec


@pytest.fixture(scope="module")
def bench():
    return spec.load_benchmark()


# config.json of XingChen-AGI/Xing4.0-29B-A4B as the model-configs catalog
# has it: every key of it stands in the configuration's file, and only the
# three that ``reduced`` lists differ.
XING_PUBLISHED = {
    "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 2,
    "hidden_act": "silu", "hidden_size": 3584, "intermediate_size": 9216,
    "kv_lora_rank": 512, "max_position_embeddings": 262144,
    "model_type": "xing4_0", "moe_intermediate_size": 1024,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 64,
    "n_shared_experts": 1, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 32,
    "num_nextn_predict_layers": 1, "hc_mult": 4, "hc_sinkhorn_iters": 20,
    "hc_eps": 1e-06, "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
    "q_lora_rank": 768, "qk_nope_head_dim": 128, "qk_rope_head_dim": 64,
    "rms_norm_eps": 1e-06, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 64,
                     "mscale": 1, "mscale_all_dim": 1,
                     "original_max_position_embeddings": 4096,
                     "type": "yarn"},
    "routed_scaling_factor": 2, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1,
    "topk_method": "noaux_tc", "v_head_dim": 128, "vocab_size": 131072}


def test_published_widths_of_xing(bench):
    xing = spec.load_json("configs", "xing4.0-29b-a4b-6l.json")
    assert (xing["hidden_size"], xing["intermediate_size"],
            xing["moe_intermediate_size"], xing["num_attention_heads"],
            xing["qk_nope_head_dim"] + xing["qk_rope_head_dim"],
            xing["v_head_dim"], xing["q_lora_rank"], xing["kv_lora_rank"],
            xing["n_routed_experts"], xing["num_experts_per_tok"],
            xing["vocab_size"]) == \
        (3584, 9216, 1024, 32, 192, 128, 768, 512, 64, 4, 131072)
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
    engine = xing["engine"]
    pages_per_sequence = (engine["max_prompt_len"]
                          + engine["max_new_tokens"]) // engine["page_size"]
    assert engine == {"page_size": 16, "max_prompt_len": 1024,
                      "max_new_tokens": 3072, "max_batch": 32,
                      "num_pages": 32 * pages_per_sequence + 1}
    assert engine["num_pages"] == 8193
    assert 0 < xing["numerics"]["logits_rtol"] < 0.05
    assert len(bench["workloads"]) == 7 and len(bench["configs"]) == 6
    assert sum(w["chips"] == 4 for w in bench["workloads"]) == 1
    mine = [m["name"] for m in bench["per_layer"]
            if m.get("workloads") == ["serve-xing-reasoning-batch"]]
    assert len(mine) == 21 and all(n.endswith(".xing") for n in mine)
