"""A stack of Kimi-Delta-Attention layers and latent layers without
positions, a dense feed-forward then experts of which the program holds a
share, in ``models/llama.py`` (ISSUE 51): prefill and decode through the
pools (latent pages for the latent layers, a state row a slot for the KDA
ones) give the training trunk's logits; what a slot held before does not
matter; the rung does not matter; ``_check``'s new allowances and what it
still refuses."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig

PAGE, SEQ, SLOTS = 4, 48, 3
MAXP = SEQ // PAGE
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=SEQ, num_layers=8, num_heads=4,
    num_kv_heads=4, embed_dim=32, mlp_dim=16, dtype=jnp.float32,
    attention="dense", remat=False, rope_theta=0.0, rms_eps=1e-5,
    num_experts=16, expert_share=(1, 4), experts_per_token=4,
    norm_topk_prob=True, router_scoring="sigmoid", router_bias=True,
    routed_scaling=2.446, shared_experts=1, first_dense_layers=1,
    dense_mlp_dim=48, kv_lora_rank=24, qk_nope_dim=8, qk_rope_dim=8,
    v_head_dim=8, layer_pattern=("linear", "linear", "linear", "full"),
    linear_heads=4, linear_key_dim=8, linear_value_dim=8, linear_gate_rank=8)
# the same stack without experts: the scan over periods with latent pages
PLAIN = dataclasses.replace(
    CFG, num_experts=0, expert_share=(0, 1), experts_per_token=0,
    norm_topk_prob=False, router_scoring="softmax", router_bias=False,
    routed_scaling=1.0, shared_experts=0, first_dense_layers=0,
    dense_mlp_dim=0, mlp_dim=48)
TABLE = 1 + np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)


def build(cfg):
    params = llama.llama_init(jax.random.PRNGKey(1), cfg)
    return params, (
        jax.jit(lambda t: llama.llama_forward(params, t, cfg)),
        jax.jit(lambda *a: llama.llama_prefill(params, cfg, *a)[:3]),
        jax.jit(lambda *a: llama.llama_decode_step(params, cfg, *a)[:3]))


@pytest.fixture(scope="module", params=["experts", "plain"])
def model(request):
    cfg = CFG if request.param == "experts" else PLAIN
    return (cfg, *build(cfg))


def pools(cfg=CFG):
    return llama.llama_init_paged_cache(cfg, SLOTS * MAXP + 1, PAGE,
                                        slots=SLOTS)


def prefill_at(prefill, tokens, length, rung, slot, kp, vp):
    padded = np.zeros((1, rung), np.int32)
    padded[0, :length] = tokens[:length]
    return prefill(padded, np.int32(length), kp, vp, TABLE[slot:slot + 1],
                   np.int32(slot))


def decode_from(decode, tokens, start, stop, slot, kp, vp):
    out = []
    for at in range(start, stop):
        tok, pos = np.zeros((SLOTS,), np.int32), np.zeros((SLOTS,), np.int32)
        tok[slot], pos[slot] = tokens[at], at
        logits, kp, vp = decode(tok, pos, kp, vp, TABLE)
        out.append(logits[slot])
    return jnp.stack(out), kp, vp


def test_the_tree_is_a_group_a_layer_with_its_own_experts():
    params = llama.llama_init(jax.random.PRNGKey(1), CFG)
    groups = params["layers"]
    assert isinstance(groups, tuple) and len(groups) == 8
    assert ["linear" in g for g in groups] == [True, True, True, False] * 2
    assert "dense_layers" not in params
    # the leading dense layer, then experts: 4 of the router's 16 held
    assert groups[0]["mlp"]["wgu"].shape == (1, 2, 32, 48)
    assert "shared" not in groups[0] and "router" not in groups[0]["mlp"]
    for group in groups[1:]:
        assert group["mlp"]["wgu"].shape == (1, 4, 2, 32, 16)
        assert group["mlp"]["router"].shape == (1, 32, 16)
        assert group["mlp"]["router_bias"].shape == (1, 16)
        assert group["shared"]["wgu"].shape == (1, 2, 32, 16)
    kda = groups[0]["linear"]
    assert {k: v.shape[1:] for k, v in kda.items()} == {
        "wqkv": (32, 96), "conv": (4, 96), "wf_a": (32, 8), "wf_b": (8, 32),
        "dt_bias": (32,), "A_log": (4,), "wb": (32, 4), "wg_a": (32, 8),
        "wg_b": (8, 32), "norm": (8,), "wo": (4, 8, 32)}
    # a latent layer projects its queries directly: no bottleneck, no norm
    assert set(groups[3]["attn"]) == {"wq", "wkv_a", "kv_a_norm", "wkv_b",
                                      "wo"}
    assert groups[3]["attn"]["wq"].shape == (1, 32, 4, 16)
    axes = llama.llama_param_axes(CFG)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)
            and all(isinstance(e, (str, type(None))) for e in a)))
    stored = llama.llama_serving_params(params, dataclasses.replace(
        CFG, dtype=jnp.bfloat16))
    assert {k: v.dtype.name for k, v in stored["layers"][1][
        "linear"].items()} == {
        "wqkv": "bfloat16", "conv": "bfloat16", "wf_a": "bfloat16",
        "wf_b": "bfloat16", "wb": "bfloat16", "wg_a": "bfloat16",
        "wg_b": "bfloat16", "wo": "bfloat16", "A_log": "float32",
        "dt_bias": "float32", "norm": "float32"}
    assert stored["layers"][3]["attn"]["wq"].dtype == jnp.bfloat16
    assert stored["layers"][3]["attn"]["kv_a_norm"].dtype == jnp.float32
    assert stored["layers"][0]["mlp"]["wgu"].dtype == jnp.bfloat16
    # the routed experts and the router are read as they are stored
    assert stored["layers"][1]["mlp"]["wgu"].dtype == jnp.float32
    assert stored["layers"][1]["shared"]["wgu"].dtype == jnp.bfloat16
    assert llama._expert_stack(CFG, stored) is stored["layers"][1]["mlp"]


def test_the_pools_are_latent_pages_beside_rows_a_slot(model):
    cfg = model[0]
    kp, vp = pools(cfg)
    assert kp.shape == (2, SLOTS * MAXP + 1, PAGE, 128)   # 2 latent layers
    assert isinstance(vp, llama.RecurrentPools) and vp.v_pages is None
    assert vp.state.shape == (6, SLOTS, 4, 8, 8)          # 6 KDA layers
    assert vp.state.dtype == jnp.float32
    assert vp.conv.shape == (6, SLOTS, 3 * 96)
    record = llama.served(cfg)
    assert record.page_kind == "latent"
    assert [a.shape for a in record.slot_rows(kp, vp)] == [
        vp.state.shape, vp.conv.shape]
    assert (record.expert_stack is None) == (not cfg.num_experts)


def test_prefill_then_decode_through_the_pools_is_the_full_forward(model):
    cfg, _, (forward, prefill, decode) = model
    tokens = np.random.default_rng(0).integers(0, 97, 40)
    want = forward(tokens[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    kp, vp = pools(cfg)
    logits, kp, vp = prefill_at(prefill, tokens, 13, 16, 1, kp, vp)
    np.testing.assert_allclose(logits[0], want[12], atol=2e-5)
    got, kp, vp = decode_from(decode, tokens, 13, 40, 1, kp, vp)
    np.testing.assert_allclose(got, want[13:40], atol=5e-5)
    # the other slots' rows were parked: still the empty state
    assert not np.asarray(vp.state[:, [0, 2]]).any()
    assert np.asarray(vp.state[:, 1]).any()


def test_the_rung_does_not_matter(model):
    cfg, _, (_, prefill, _) = model
    tokens = np.random.default_rng(1).integers(0, 97, 16)
    out = [prefill_at(prefill, tokens, 11, rung, 0, *pools(cfg))
           for rung in (12, 16, 32)]
    for logits, _, vp in out[1:]:
        np.testing.assert_allclose(logits, out[0][0], atol=1e-5)
        np.testing.assert_allclose(vp.state[:, 0], out[0][2].state[:, 0],
                                   atol=1e-5)
        np.testing.assert_allclose(vp.conv[:, 0], out[0][2].conv[:, 0],
                                   atol=1e-6)


def test_a_slots_second_sequence_does_not_see_the_firsts_state(model):
    cfg, _, (forward, prefill, decode) = model
    rng = np.random.default_rng(2)
    first, second = rng.integers(0, 97, 30), rng.integers(0, 97, 24)
    kp, vp = pools(cfg)
    _, kp, vp = prefill_at(prefill, first, 20, 32, 2, kp, vp)
    _, kp, vp = decode_from(decode, first, 20, 30, 2, kp, vp)
    held = np.asarray(vp.state[:, 2])
    logits, kp, vp = prefill_at(prefill, second, 9, 12, 2, kp, vp)
    assert np.abs(np.asarray(vp.state[:, 2]) - held).max() > 1e-3
    want = forward(second[None])[0]
    np.testing.assert_allclose(logits[0], want[8], atol=2e-5)
    got, _, _ = decode_from(decode, second, 9, 24, 2, kp, vp)
    np.testing.assert_allclose(got, want[9:24], atol=5e-5)


def test_the_programs_count_the_held_experts_load():
    params = llama.llama_init(jax.random.PRNGKey(1), CFG)
    tokens = np.random.default_rng(3).integers(0, 97, 16)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :11] = tokens[:11]
    *_, load = llama.llama_prefill(params, CFG, padded, np.int32(11),
                                   *pools(), TABLE[:1], np.int32(0))
    assert load.shape == (7, 4) and load.dtype == jnp.int32
    made = 11 * 4                      # a layer: real positions x top-4
    assert 0 < int(load.sum()) < 7 * made
    # the four shares' loads, side by side, are every assignment made
    loads = []
    for share in range(4):
        cfg = dataclasses.replace(CFG, expert_share=(share, 4))
        *_, part = llama.llama_prefill(
            llama.llama_init(jax.random.PRNGKey(1), cfg), cfg, padded,
            np.int32(11), *pools(), TABLE[:1], np.int32(0))
        loads.append(part[0])          # the first expert layer's input is
    assert int(sum(a.sum() for a in loads)) == made   # every share's alike


def test_the_decay_neither_forgets_at_once_nor_never():
    """alpha's median over seeded weights and inputs lies inside (0.5,
    0.999) in every layer, and the channels of a head differ."""
    from ray_tpu.ops.linear_attention import kda_gate
    params = llama.llama_init(jax.random.PRNGKey(1), CFG)
    x = jax.random.normal(jax.random.PRNGKey(5), (64, 32)) * 2.0
    for group in params["layers"]:
        if "linear" not in group:
            continue
        a = jax.tree.map(lambda leaf: leaf[0], group["linear"])
        alpha = jnp.exp(kda_gate((x @ a["wf_a"]) @ a["wf_b"], a["A_log"],
                                 a["dt_bias"]))
        assert alpha.shape == (64, 4, 8)
        assert 0.5 < float(jnp.median(alpha)) < 0.999
        assert float(jnp.std(alpha, axis=-1).mean()) > 1e-3


def test_what_is_not_written_refuses_with_a_message():
    params = llama.llama_init(jax.random.PRNGKey(1), CFG)
    with pytest.raises(NotImplementedError, match="chunked scan's backward"):
        llama.llama_loss(params, {"tokens": jnp.zeros((2, 9), jnp.int32)},
                         CFG)
    with pytest.raises(NotImplementedError, match="not written for a model"):
        llama.llama_prefill_attention(
            dataclasses.replace(CFG, attention="flash"), 16)


@pytest.mark.parametrize("change", [
    {},                                               # Kimi Linear's stack
    {"expert_share": (0, 1)},                         # every expert held
    {"linear_gate_rank": 0},                          # the scalar decay
    {"kv_lora_rank": 0, "qk_nope_dim": 0, "qk_rope_dim": 0,
     "v_head_dim": 0},                                # K/V pages, experts
    {"first_dense_layers": 2},
])
def test_what_check_allows_now(change):
    llama._check(dataclasses.replace(CFG, **change))
    llama._check(dataclasses.replace(PLAIN, **{
        k: v for k, v in change.items()
        if k not in ("expert_share", "first_dense_layers")}))


@pytest.mark.parametrize("cfg,change,message", [
    # (experts with no leading dense layer serve since PR 61; a stack that
    # mixes two of the slot kinds is still refused)
    (CFG, {"layer_pattern": ("linear", "ssm", "linear", "full")},
     "ONE other kind"),
    (CFG, {"hc_mult": 4}, "not written for"),
    (CFG, {"ut_steps": 2}, "not written for"),
    (CFG, {"block_length": 4, "denoise_steps": 2}, "not written for"),
    (CFG, {"linear_neg_eigval": True}, "beta in \\(0, 1\\)"),
    (CFG, {"expert_share": (4, 4)}, "share i of n"),
    (CFG, {"expert_share": (0, 3)}, "share i of n"),
    (PLAIN, {"expert_share": (0, 2)}, "share i of n"),
    (CFG, {"rope_theta": 10000.0}, "rotates by YaRN's tables"),
    (CFG, {"rope_yarn": (40.0, 4096.0, 32.0, 1.0, 1.0, 1.0)},
     "rotates by YaRN's tables"),
    (CFG, {"qk_nope_dim": 0}, "latent attention needs"),
    (PLAIN, {"layer_pattern": (), "linear_heads": 0},
     "linear_gate_rank belongs to a layer_pattern"),
    (CFG, {"num_layers": 6}, "whole periods"),
])
def test_what_check_still_refuses(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        llama._check(dataclasses.replace(cfg, **change))
