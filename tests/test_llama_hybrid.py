"""A stack of linear-attention and full-attention layers in
``models/llama.py`` (ISSUE 48): prefill and decode through the pools (K/V
pages for the full layers, a state row a slot for the linear ones) give the
training trunk's logits; what a slot held before does not matter; the rung
does not matter; what is not written refuses with a message."""

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
    num_kv_heads=4, embed_dim=32, mlp_dim=48, dtype=jnp.float32,
    attention="dense", remat=False, rope_theta=0.0, rms_eps=1e-6,
    qk_norm=True, pre_norm=False, post_norm=True,
    layer_pattern=("linear", "linear", "linear", "full"), linear_heads=2,
    linear_key_dim=8, linear_value_dim=192, linear_neg_eigval=True)
TABLE = 1 + np.arange(SLOTS * MAXP, dtype=np.int32).reshape(SLOTS, MAXP)


@pytest.fixture(scope="module")
def params():
    return llama.llama_init(jax.random.PRNGKey(1), CFG)


@pytest.fixture(scope="module")
def programs(params):
    return (jax.jit(lambda t: llama.llama_forward(params, t, CFG)),
            jax.jit(lambda *a: llama.llama_prefill(params, CFG, *a)),
            jax.jit(lambda *a: llama.llama_decode_step(params, CFG, *a)))


def pools():
    return llama.llama_init_paged_cache(CFG, SLOTS * MAXP + 1, PAGE,
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


def test_the_tree_is_a_group_a_position_of_the_pattern(params):
    groups = params["layers"]
    assert isinstance(groups, tuple) and len(groups) == 4
    assert ["linear" in g for g in groups] == [True, True, True, False]
    assert "attn" in groups[3] and "ln1" not in groups[3]
    assert groups[0]["linear"]["wqkv"].shape == (2, 32, 2 * (2 * 8 + 192))
    assert groups[0]["linear"]["conv"].shape == (2, 4, 2 * (2 * 8 + 192))
    axes = llama.llama_param_axes(CFG)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)
            and all(isinstance(e, (str, type(None))) for e in a)))
    stored = llama.llama_serving_params(params, dataclasses.replace(
        CFG, dtype=jnp.bfloat16))
    linear = stored["layers"][0]["linear"]
    assert {k: v.dtype.name for k, v in linear.items()} == {
        "wqkv": "bfloat16", "wz": "bfloat16", "wba": "bfloat16",
        "conv": "bfloat16", "wo": "bfloat16", "A_log": "float32",
        "dt_bias": "float32", "norm": "float32"}
    assert stored["layers"][3]["ln1_post"]["scale"].dtype == jnp.float32


def test_the_pools_are_pages_for_the_full_layers_and_rows_for_the_rest():
    kp, vp = pools()
    assert kp.shape == (2, SLOTS * MAXP + 1, PAGE, 32)       # 2 full layers
    assert isinstance(vp, llama.RecurrentPools)
    assert vp.v_pages.shape == kp.shape
    assert vp.state.shape == (6, SLOTS, 3, 8, 128)           # 6 linear
    assert vp.state.dtype == jnp.float32
    assert vp.conv.shape == (6, SLOTS, 3 * 2 * (2 * 8 + 192))
    with pytest.raises(ValueError, match="how many slots"):
        llama.llama_init_paged_cache(CFG, 9, PAGE)


def test_prefill_then_decode_through_the_pools_is_the_full_forward(
        programs):
    forward, prefill, decode = programs
    tokens = np.random.default_rng(0).integers(0, 97, 40)
    want = forward(tokens[None])[0]
    assert float(jnp.abs(want).max()) > 0.1
    kp, vp = pools()
    logits, kp, vp = prefill_at(prefill, tokens, 13, 16, 1, kp, vp)
    np.testing.assert_allclose(logits[0], want[12], atol=2e-5)
    got, kp, vp = decode_from(decode, tokens, 13, 40, 1, kp, vp)
    np.testing.assert_allclose(got, want[13:40], atol=5e-5)
    # the other slots' rows were parked: still the empty state
    assert not np.asarray(vp.state[:, [0, 2]]).any()
    assert np.asarray(vp.state[:, 1]).any()


def test_the_rung_does_not_matter(programs):
    """A longer rung's padded tail leaves logits, state and convolution
    tail as the shorter rung's: as they stand after ``length - 1``."""
    _, prefill, _ = programs
    tokens = np.random.default_rng(1).integers(0, 97, 16)
    out = [prefill_at(prefill, tokens, 11, rung, 0, *pools())
           for rung in (12, 16, 32)]
    for logits, _, vp in out[1:]:
        np.testing.assert_allclose(logits, out[0][0], atol=1e-5)
        np.testing.assert_allclose(vp.state[:, 0], out[0][2].state[:, 0],
                                   atol=1e-5)
        np.testing.assert_allclose(vp.conv[:, 0], out[0][2].conv[:, 0],
                                   atol=1e-6)


def test_a_slots_second_sequence_does_not_see_the_firsts_state(programs):
    forward, prefill, decode = programs
    rng = np.random.default_rng(2)
    first, second = rng.integers(0, 97, 30), rng.integers(0, 97, 24)
    kp, vp = pools()
    _, kp, vp = prefill_at(prefill, first, 20, 32, 2, kp, vp)
    _, kp, vp = decode_from(decode, first, 20, 30, 2, kp, vp)
    held = np.asarray(vp.state[:, 2])
    logits, kp, vp = prefill_at(prefill, second, 9, 12, 2, kp, vp)
    assert np.abs(np.asarray(vp.state[:, 2]) - held).max() > 1e-3
    want = forward(second[None])[0]
    np.testing.assert_allclose(logits[0], want[8], atol=2e-5)
    got, _, _ = decode_from(decode, second, 9, 24, 2, kp, vp)
    np.testing.assert_allclose(got, want[9:24], atol=5e-5)


def test_the_decay_neither_forgets_at_once_nor_never(params):
    """alpha's median over seeded weights and inputs lies inside (0.5,
    0.999): a state that forgets in two positions, or never, would hide
    faults in the decay."""
    from ray_tpu.ops.linear_attention import decay_and_beta
    forward_x = jax.random.normal(jax.random.PRNGKey(5), (64, 32)) * 2.0
    medians = []
    for group in params["layers"][:3]:
        a = group["linear"]
        ba = forward_x @ a["wba"][0]
        g, beta = decay_and_beta(ba[:, 2:], ba[:, :2], a["A_log"][0],
                                 a["dt_bias"][0], True)
        medians.append(float(jnp.median(jnp.exp(g))))
        assert 0 < float(beta.min()) and float(beta.max()) < 2
    assert all(0.5 < m < 0.999 for m in medians), medians


def test_what_is_not_written_refuses_with_a_message(params):
    tokens = jnp.zeros((2, 9), jnp.int32)
    with pytest.raises(NotImplementedError, match="chunked scan's backward"):
        llama.llama_loss(params, {"tokens": tokens}, CFG)
    block = dataclasses.replace(CFG, block_length=4, denoise_steps=2,
                                mask_token=96)
    state = (jnp.zeros((SLOTS, 4), jnp.int32), jnp.ones((SLOTS, 4), bool),
             jnp.zeros((SLOTS,), jnp.int32), jnp.zeros((SLOTS,), jnp.int32))
    with pytest.raises(NotImplementedError, match="block step keeps K/V"):
        llama.llama_block_step(params, block, state,
                               jnp.full((SLOTS,), 8, jnp.int32), *pools(),
                               jnp.asarray(TABLE))


@pytest.mark.parametrize("change,message", [
    ({"block_length": 4, "denoise_steps": 2}, "not written for"),
    ({"ut_steps": 2}, "not written for"),
    # (experts in every layer of a pattern stack serve since PR 61)
    ({"hc_mult": 2}, "not written for"),
    ({"layer_pattern": ("full", "full")}, "at least one of them linear"),
    ({"layer_pattern": ("linear", "window")}, "one period of"),
    ({"num_layers": 6}, "whole periods"),
    ({"linear_heads": 0}, "linear layers need"),
])
def test_a_pattern_the_program_cannot_run_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        llama._check(dataclasses.replace(CFG, **change))


def test_a_model_that_rotates_nothing_and_norms_outputs_only():
    """The two switches by themselves, on the plain stack: ``rope_theta`` 0
    leaves q and k as they are, and without ``pre_norm`` the tree has no
    input norms and the paged decode is still the full forward."""
    cfg = dataclasses.replace(CFG, layer_pattern=(), linear_heads=0,
                              linear_key_dim=0, linear_value_dim=0,
                              num_layers=2)
    params = llama.llama_init(jax.random.PRNGKey(2), cfg)
    assert "ln1" not in params["layers"] and "ln1_post" in params["layers"]
    assert llama._rope_tables(cfg, 8) == (None, None)
    tokens = np.random.default_rng(3).integers(0, 97, 20)
    want = llama.llama_forward(params, tokens[None], cfg)[0]
    kp, vp = llama.llama_init_paged_cache(cfg, MAXP + 1, PAGE)
    padded = np.zeros((1, 16), np.int32)
    padded[0, :10] = tokens[:10]
    table = jnp.asarray(1 + np.arange(MAXP, dtype=np.int32)[None])
    logits, kp, vp = llama.llama_prefill(params, cfg, jnp.asarray(padded),
                                         jnp.int32(10), kp, vp, table)
    np.testing.assert_allclose(logits[0], want[9], atol=2e-5)
    logits, kp, vp = llama.llama_decode_step(
        params, cfg, jnp.asarray(tokens[10:11]), jnp.array([10], jnp.int32), kp,
        vp, table)
    np.testing.assert_allclose(logits[0], want[10], atol=2e-5)
