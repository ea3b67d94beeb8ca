"""LFM2's block in ``models/llama.py`` (ISSUE 55): a ``layer_pattern`` with
the THIRD kind, "conv" (``_conv_operator``: a gated short convolution over 3
positions with no activation), whose only kept past beside the attention
layers' K/V pages is a convolution tail a decode slot
(``RecurrentPools.state`` None); experts behind one leading dense layer with
NO shared expert; the head tied to the table; the router's renormalisation
over ``router_norm_eps``.  Prefill then decode through the pools equals
``llama_forward`` whatever the rung and the slot, with experts (a group a
layer, unrolled) and without (groups scanned over periods)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig

PAGE, SEQ = 8, 48
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=SEQ, num_layers=6, num_heads=4, num_kv_heads=2,
    head_size=16, embed_dim=64, mlp_dim=24, dtype=jnp.float32,
    attention="dense", rope_theta=1e6, qk_norm_per_head=True,
    num_experts=8, experts_per_token=4, norm_topk_prob=True,
    router_scoring="sigmoid", router_bias=True, router_norm_eps=1e-6,
    first_dense_layers=1, dense_mlp_dim=96, linear_conv=3,
    layer_pattern=("conv", "full", "conv", "conv", "conv", "full"),
    tie_embeddings=True)
# without experts the pattern is scanned over periods: two of three
PLAIN = dataclasses.replace(
    CFG, num_experts=0, experts_per_token=0, norm_topk_prob=False,
    router_scoring="softmax", router_bias=False, first_dense_layers=0,
    dense_mlp_dim=0, mlp_dim=96, layer_pattern=("conv", "conv", "full"))


def build(cfg):
    """Seeded weights with the operators' and feed-forwards' ways out eight
    times, and ``win`` twelve times, the initialisation's: at its scale the
    gates ``B``, ``C`` and ``z`` are near zero and a tiny model answers one
    token whatever its tails hold."""
    tree = llama.llama_init(jax.random.PRNGKey(1), cfg)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: {"wo": 8.0, "wd": 8.0, "wout": 8.0, "win": 12.0}.get(
            getattr(path[-1], "key", ""), 1.0) * a, tree)


@pytest.fixture(scope="module", params=["experts", "plain"])
def model(request):
    cfg = CFG if request.param == "experts" else PLAIN
    params = build(cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 30), 0, 97)
    full = jax.jit(lambda t: llama.llama_forward(params, t, cfg))(tokens)
    prefill = jax.jit(lambda *a: llama.llama_prefill(params, cfg, *a)[:3])
    decode = jax.jit(lambda *a: llama.llama_decode_step(params, cfg, *a)[:3])
    return cfg, params, tokens, full, prefill, decode


def pools(cfg=CFG, slots=3):
    return llama.llama_init_paged_cache(cfg, 3 * (SEQ // PAGE) + 1, PAGE,
                                        None, slots)


def table_of(slot):
    return jnp.arange(1 + slot * (SEQ // PAGE),
                      1 + (slot + 1) * (SEQ // PAGE), dtype=jnp.int32)


def through_the_pools(model, length, rung, slot, kp, vp, stop=30):
    """Max |logits - forward's| over the prefill's position and the decode
    positions up to ``stop``, and the pools."""
    cfg, params, tokens, full, prefill, decode = model
    padded = jnp.full((1, rung), 5, jnp.int32).at[0, :length].set(
        tokens[0, :length])
    logits, kp, vp = prefill(padded, jnp.int32(length), kp, vp,
                             table_of(slot)[None], jnp.int32(slot))
    errs = [float(jnp.abs(logits[0] - full[0, length - 1]).max())]
    table = jnp.zeros((3, SEQ // PAGE), jnp.int32).at[slot].set(
        table_of(slot))
    for t in range(length, stop):
        tok = jnp.zeros((3,), jnp.int32).at[slot].set(tokens[0, t])
        pos = jnp.zeros((3,), jnp.int32).at[slot].set(t)
        logits, kp, vp = decode(tok, pos, kp, vp, table)
        errs.append(float(jnp.abs(logits[slot] - full[0, t]).max()))
    return max(errs), kp, vp


def test_the_tree_has_conv_groups_a_tied_head_and_no_shared_expert():
    shapes = jax.eval_shape(lambda: llama.llama_init(
        jax.random.PRNGKey(0), CFG))
    assert "lm_head" not in shapes and shapes["wte"].shape == (97, 64)
    groups = shapes["layers"]
    assert ["conv" in g for g in groups] == [True, False, True, True, True,
                                             False]
    conv = groups[0]["conv"]
    assert {k: v.shape for k, v in conv.items()} == {
        "win": (1, 64, 192), "taps": (1, 3, 64), "wout": (1, 64, 64)}
    assert groups[0]["mlp"]["wgu"].shape == (1, 2, 64, 96)     # dense
    for group in groups[1:]:
        assert group["mlp"]["wgu"].shape == (1, 8, 2, 64, 24)
        assert group["mlp"]["router"].shape == (1, 64, 8)
        assert "shared" not in group
    assert groups[1]["attn"]["q_norm"].shape == (1, 16)       # a head's
    axes = llama.llama_param_axes(CFG)
    assert "lm_head" not in axes
    assert jax.tree.structure(axes, is_leaf=lambda a: isinstance(a, tuple)
                              and all(isinstance(x, (str, type(None)))
                                      for x in a)) == \
        jax.tree.structure(shapes)
    stored = jax.eval_shape(lambda: llama.llama_serving_params(
        llama.llama_init(jax.random.PRNGKey(0), dataclasses.replace(
            CFG, dtype=jnp.bfloat16)), dataclasses.replace(
                CFG, dtype=jnp.bfloat16)))
    assert stored["wte"].dtype == jnp.bfloat16
    assert stored["layers"][0]["conv"]["taps"].dtype == jnp.bfloat16
    assert stored["layers"][1]["mlp"]["router"].dtype == jnp.float32
    # the periods' stack: a group a position, each over two periods
    plain = jax.eval_shape(lambda: llama.llama_init(
        jax.random.PRNGKey(0), PLAIN))
    assert [g["conv" if "conv" in g else "attn"][
        "win" if "conv" in g else "wq"].shape[0]
        for g in plain["layers"]] == [2, 2, 2]


def test_the_pools_are_pages_beside_tails_and_no_state(model):
    cfg = model[0]
    kp, vp = pools(cfg)
    full_layers = cfg.layer_pattern.count("full") * (
        cfg.num_layers // len(cfg.layer_pattern))
    assert kp.shape == vp.v_pages.shape == (full_layers, 19, PAGE, 2 * 16)
    assert vp.state is None
    assert vp.conv.shape == (cfg.num_layers - full_layers, 3, 2 * 64)
    record = llama.served(cfg)
    assert [a.shape for a in record.slot_rows(kp, vp)] == [vp.conv.shape]
    assert record.conv_tails(kp, vp) is vp.conv
    assert record.linear_state is None and record.page_kind == "kv"
    assert (record.expert_stack is None) == (not cfg.num_experts)
    with pytest.raises(ValueError, match="how many slots"):
        llama.llama_init_paged_cache(cfg, 9, PAGE)


@pytest.mark.parametrize("length,rung", [(1, 8), (2, 16), (11, 16),
                                         (16, 16), (19, 32)])
def test_prefill_then_decode_through_the_pools_is_the_full_forward(
        model, length, rung):
    """Prompts of 1 and 2 (shorter than the tail or just it), one that fills
    its rung and two that do not, in slot 1 of three: prefill and every
    decode position to the 30th is ``llama_forward``'s."""
    err, kp, vp = through_the_pools(model, length, rung, 1, *pools(model[0]))
    assert err < 2e-4
    # the other slots' tails were left alone
    assert not np.asarray(vp.conv[:, 0]).any()
    assert not np.asarray(vp.conv[:, 2]).any()
    assert np.asarray(vp.conv[:, 1]).any()


def test_a_slots_second_sequence_does_not_see_the_firsts_tail(model):
    """Slot 2 serves one sequence to its end and then another prompt: the
    second's numbers are those of a fresh pool."""
    _, kp, vp = through_the_pools(model, 13, 16, 2, *pools(model[0]))
    err, _, _ = through_the_pools(model, 3, 8, 2, kp, vp, stop=12)
    assert err < 2e-4


def test_a_stale_tail_is_seen(model, monkeypatch):
    """The likeliest slips of the hand-over, planted: the tail taken at the
    rung's end (where the padding lies), and one position early."""
    from ray_tpu.ops import linear_attention as la
    cfg, params = model[:2]
    real = la.conv_tail
    for stale in (lambda x, length, K: real(x, x.shape[0], K),
                  lambda x, length, K: real(x, length - 1, K)):
        monkeypatch.setattr(la, "conv_tail", stale)
        prefill = jax.jit(lambda *a: llama.llama_prefill(params, cfg, *a)[:3])
        err, _, _ = through_the_pools(
            (*model[:4], prefill, model[5]), 11, 16, 0, *pools(cfg), stop=14)
        assert err > 1e-2


def test_the_tied_head_is_the_table(model):
    cfg, params, tokens, full = model[:4]
    hidden = llama.llama_hidden(params, tokens, cfg)
    np.testing.assert_allclose(full, hidden @ params["wte"].T, atol=1e-5)
    untied = dataclasses.replace(cfg, tie_embeddings=False)
    tree = {**params, "lm_head": params["wte"].T}
    np.testing.assert_allclose(llama.llama_forward(tree, tokens, untied),
                               full, atol=1e-5)
    # one leaf fewer: V x D parameters lighter
    count = lambda c: sum(a.size for a in jax.tree.leaves(   # noqa: E731
        jax.eval_shape(lambda: llama.llama_init(jax.random.PRNGKey(0), c))))
    assert count(untied) - count(cfg) == 97 * 64


def test_the_routers_epsilon_is_the_configurations():
    """Gates over their sum + eps: 1e-6 moves them where 1e-20 does not, by
    what the sum of four sigmoids makes of it."""
    from ray_tpu.ops.moe import _route
    logits = jax.random.normal(jax.random.PRNGKey(0), (5, 8)) - 6.0
    bias = jnp.zeros((8,))
    g20, e20 = _route(logits, bias, 4, "sigmoid", True, 1.0)
    g6, e6 = _route(logits, bias, 4, "sigmoid", True, 1.0, norm_eps=1e-6)
    np.testing.assert_array_equal(e20, e6)
    np.testing.assert_allclose(g20.sum(-1), 1.0, rtol=1e-6)
    scores = jnp.take_along_axis(jax.nn.sigmoid(logits), e6, axis=-1)
    np.testing.assert_allclose(g6, scores / (scores.sum(-1, keepdims=True)
                                             + 1e-6), rtol=1e-6)
    assert float(jnp.abs(g6.sum(-1) - 1.0).max()) > 1e-5


@pytest.mark.parametrize("change", [
    {"layer_pattern": ("conv", "full"), "num_layers": 6},
    {"layer_pattern": ("conv",) * 6},
    {"linear_conv": 2},
    {"tie_embeddings": False},
    {"router_norm_eps": 1e-20}])
def test_what_check_allows_now(change):
    llama._check(dataclasses.replace(CFG, **change))
    llama._check(dataclasses.replace(PLAIN, tie_embeddings=False))


@pytest.mark.parametrize("cfg,change,message", [
    (CFG, {"layer_pattern": ("conv", "linear", "full")}, "ONE other kind"),
    (CFG, {"layer_pattern": ("window", "full", "conv")}, "ONE other kind"),
    (CFG, {"layer_pattern": ("full",) * 6}, "at least one"),
    (CFG, {"num_layers": 7}, "whole periods"),
    (CFG, {"linear_conv": 1}, "linear_conv of 2 or more"),
    (CFG, {"linear_heads": 4}, "linear_conv alone"),
    (CFG, {"kv_lora_rank": 8, "qk_nope_dim": 8, "qk_rope_dim": 8,
           "v_head_dim": 8, "rope_theta": 0.0, "head_size": 0,
           "qk_norm_per_head": False}, "no kv_lora_rank"),
    # (experts with no leading dense layer serve since PR 61; a
    # hyper-connected stream under a pattern is still refused)
    (CFG, {"hc_mult": 2}, "hc_mult"),
    (CFG, {"ut_steps": 2}, "ut_steps"),
    (PLAIN, {"block_length": 4, "denoise_steps": 2}, "block_length"),
    (CFG, {"layer_pattern": ("linear", "full") * 3}, "linear_heads")])
def test_what_check_still_refuses(cfg, change, message):
    with pytest.raises(ValueError, match=message):
        llama._check(dataclasses.replace(cfg, **change))


def test_what_is_not_written_refuses_with_a_message():
    params = jax.eval_shape(lambda: llama.llama_init(
        jax.random.PRNGKey(0), PLAIN))
    with pytest.raises(NotImplementedError, match="conv"):
        llama.llama_loss(params, {"tokens": jnp.zeros((1, 9), jnp.int32)},
                         PLAIN)
