"""A looped model through ``models/llama.py`` at a tiny size on the CPU:
``ut_steps`` passes over one set of layers, the final norm after every
pass, a norm on every sublayer's output (``post_norm``), and a pool layer
for every (pass, layer)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig

T, L, PAGE, PAGES, MAXP, BATCH, PROMPT = 3, 3, 8, 13, 3, 4, 16
CFG = LlamaConfig(vocab_size=256, max_seq_len=MAXP * PAGE, num_layers=L,
                  num_heads=4, num_kv_heads=4, embed_dim=64, mlp_dim=96,
                  rope_theta=1e6, rms_eps=1e-6, dtype=jnp.float32,
                  attention="dense", remat=False, ut_steps=T,
                  post_norm=True)
ROW = np.array([[5, 2, 9]], np.int32)          # the sequence's pages
LENGTH = 11


@pytest.fixture(scope="module")
def params():
    p = llama.llama_init(jax.random.PRNGKey(0), CFG)
    for n, name in enumerate(("ln1_post", "ln2_post")):
        p["layers"][name] = {"scale": 1 + 0.3 * jax.random.normal(
            jax.random.PRNGKey(n + 1), (L, 64))}
    return p


def prompt(pad=0):
    tokens = np.full((1, PROMPT), pad, np.int32)
    tokens[0, :LENGTH] = np.arange(LENGTH) * 7 % 256
    return tokens


def prefill(params, cfg=CFG, pad=0):
    kp, vp = llama.llama_init_paged_cache(cfg, PAGES, PAGE)
    return jax.jit(lambda p, *a: llama.llama_prefill(p, cfg, *a))(
        params, prompt(pad), np.int32(LENGTH), kp, vp, ROW)


def decode(params, kp, vp, cfg=CFG, others=False):
    """One step with slot 0 at the position after the prompt; ``others``
    makes the other slots live on pages of their own."""
    token = np.zeros((BATCH,), np.int32)
    pos = np.zeros((BATCH,), np.int32)
    table = np.zeros((BATCH, MAXP), np.int32)
    token[0], pos[0], table[0] = 41, LENGTH, ROW[0]
    if others:
        token[1:], pos[1:] = (17, 99, 3), (3, 9, 1)
        table[1:] = [[1, 3, 4], [6, 7, 8], [10, 11, 12]]
    return jax.jit(lambda p, *a: llama.llama_decode_step(p, cfg, *a))(
        params, token, pos, kp, vp, table)


def test_the_pool_has_a_layer_for_every_pass_and_layer():
    kp, vp = llama.llama_init_paged_cache(CFG, PAGES, PAGE)
    assert kp.shape == vp.shape == (T * L, PAGES, PAGE, 64)
    kp, _ = llama.llama_init_paged_cache(
        dataclasses.replace(CFG, ut_steps=1), PAGES, PAGE)
    assert kp.shape[0] == L


def test_pass_t_layer_l_writes_pool_layer_tL_plus_l_and_no_other(params):
    """A model of fewer passes (or, in the first pass, of fewer layers)
    computes the same keys and values as far as it goes, so its pool has to
    be the leading pool layers of the whole model's; and every pool layer
    holds something of its own in the sequence's pages and nothing
    elsewhere but the scratch page."""
    _, kp, vp = prefill(params)
    for pool in (kp, vp):
        pool = np.asarray(pool)
        assert not pool[:, [p for p in range(1, PAGES)
                            if p not in ROW[0]]].any()
        held = pool[:, ROW[0]].reshape(T * L, MAXP * PAGE, 64)
        assert np.abs(held[:, :LENGTH]).min(axis=(1, 2)).all()
        assert not held[:, LENGTH:].any()
        assert len({held[n].tobytes() for n in range(T * L)}) == T * L
    for passes in range(1, T):
        _, fewer, _ = prefill(params, dataclasses.replace(
            CFG, ut_steps=passes))
        np.testing.assert_allclose(fewer[:, 1:], kp[:passes * L, 1:],
                                   rtol=1e-6, atol=1e-6)
    for layers in range(1, L):
        cut = jax.tree.map(lambda a: a[:layers], params["layers"])
        _, fewer, _ = prefill({**params, "layers": cut}, dataclasses.replace(
            CFG, ut_steps=1, num_layers=layers))
        np.testing.assert_allclose(fewer[:, 1:], kp[:layers, 1:],
                                   rtol=1e-6, atol=1e-6)
    # a decode step: one position of the live slot, in every pool layer
    _, kp2, vp2 = decode(params, kp, vp)
    changed = np.argwhere(np.asarray(kp2 != kp)[:, 1:].any(axis=-1))
    assert {tuple(c) for c in changed} == {
        (n, ROW[0][LENGTH // PAGE] - 1, LENGTH % PAGE)
        for n in range(T * L)}
    _, fewer, _ = decode(params, kp[:2 * L], vp[:2 * L],
                         dataclasses.replace(CFG, ut_steps=2))
    np.testing.assert_allclose(fewer[:, 1:], kp2[:2 * L, 1:],
                               rtol=1e-6, atol=1e-6)


def test_prefill_then_decode_is_the_full_forward(params):
    tokens = np.append(prompt()[0, :LENGTH], 41)[None]
    want = llama.llama_forward(params, tokens, CFG)[0]
    logits, kp, vp = prefill(params)
    np.testing.assert_allclose(logits[0], want[LENGTH - 1], rtol=2e-4,
                               atol=2e-5)
    logits, _, _ = decode(params, kp, vp)
    np.testing.assert_allclose(logits[0], want[LENGTH], rtol=2e-4,
                               atol=2e-5)


def test_idle_slots_and_prompt_padding_change_nothing(params):
    logits, kp, vp = prefill(params)
    other, kp_other, vp_other = prefill(params, pad=201)
    np.testing.assert_array_equal(logits, other)
    np.testing.assert_array_equal(kp[:, 1:], kp_other[:, 1:])
    np.testing.assert_array_equal(vp[:, 1:], vp_other[:, 1:])
    alone, _, _ = decode(params, kp, vp)
    crowded, _, _ = decode(params, kp, vp, others=True)
    np.testing.assert_allclose(alone[0], crowded[0], rtol=1e-6, atol=1e-6)
    assert np.abs(np.asarray(crowded[1:])).max() > 0


def test_every_pass_and_both_post_norms_matter(params):
    tokens = prompt()[:, :LENGTH]
    whole = llama.llama_forward(params, tokens, CFG)
    for change in ({"ut_steps": T - 1}, {"post_norm": False}):
        other = llama.llama_forward(
            params, tokens, dataclasses.replace(CFG, **change))
        assert float(jnp.linalg.norm(other - whole)
                     / jnp.linalg.norm(whole)) > 0.1


def scans(jaxpr, depth=0):
    """The nesting depths of every ``scan`` in the jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        inner = depth + (eqn.primitive.name == "scan")
        if eqn.primitive.name == "scan":
            found.append(inner)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += scans(sub, inner)
    return found


@pytest.mark.parametrize("program", ["hidden", "prefill", "decode"])
def test_one_pass_and_no_post_norm_wrap_nothing(program):
    """``ut_steps=1, post_norm=False`` is the plain model: the same jaxpr as
    a configuration that never heard of either, one scan deep, no
    ``loop_norm``; the looped model's layer scan sits inside a pass scan."""
    def traced(cfg):
        p = jax.eval_shape(lambda: llama.llama_init(jax.random.PRNGKey(0),
                                                    cfg))
        kp, vp = jax.eval_shape(
            lambda: llama.llama_init_paged_cache(cfg, PAGES, PAGE))
        ints = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)   # noqa: E731
        fn, args = {
            "hidden": (llama.llama_hidden, (ints(2, PROMPT),)),
            "prefill": (llama.llama_prefill,
                        (ints(1, PROMPT), ints(), kp, vp, ints(1, MAXP))),
            "decode": (llama.llama_decode_step,
                       (ints(BATCH), ints(BATCH), kp, vp,
                        ints(BATCH, MAXP)))}[program]
        if program == "hidden":
            return jax.make_jaxpr(lambda p, t: fn(p, t, cfg))(p, *args)
        return jax.make_jaxpr(lambda p, *a: fn(p, cfg, *a))(p, *args)

    fields = {f.name: getattr(CFG, f.name) for f in dataclasses.fields(CFG)
              if f.name not in ("ut_steps", "post_norm")}
    plain = traced(LlamaConfig(**fields))
    explicit = traced(dataclasses.replace(CFG, ut_steps=1, post_norm=False))
    assert str(plain) == str(explicit)
    assert scans(plain.jaxpr) == [1]
    looped = traced(CFG)
    assert scans(looped.jaxpr) == [1, 2]
    lowered = lambda cfg: jax.jit(   # noqa: E731
        lambda p, t: llama.llama_hidden(p, t, cfg)).lower(
            jax.eval_shape(lambda: llama.llama_init(jax.random.PRNGKey(0),
                                                    cfg)),
            jax.ShapeDtypeStruct((2, PROMPT), jnp.int32)).as_text(
                debug_info=True)
    assert "loop_norm" in lowered(CFG)
    assert "loop_norm" not in lowered(dataclasses.replace(
        CFG, ut_steps=1, post_norm=False))


@pytest.mark.parametrize("cfg", [
    CFG, dataclasses.replace(CFG, ut_steps=1, post_norm=False),
    dataclasses.replace(CFG, post_norm=False),
    dataclasses.replace(CFG, qk_norm=True, num_experts=4,
                        experts_per_token=2)])
def test_init_and_axes_have_the_same_leaves(cfg):
    shapes = jax.eval_shape(lambda: llama.llama_init(jax.random.PRNGKey(0),
                                                     cfg))
    axes = llama.llama_param_axes(cfg)
    is_axes = lambda a: isinstance(a, tuple)   # noqa: E731
    assert jax.tree.structure(shapes) == jax.tree.structure(
        axes, is_leaf=is_axes)
    for leaf, ax in zip(jax.tree.leaves(shapes),
                        jax.tree.leaves(axes, is_leaf=is_axes)):
        assert len(leaf.shape) == len(ax)
    assert ("ln1_post" in shapes["layers"]) == cfg.post_norm
    stored = jax.eval_shape(lambda: llama.llama_serving_params(
        llama.llama_init(jax.random.PRNGKey(0), cfg), cfg))
    if cfg.post_norm:
        assert stored["layers"]["ln2_post"]["scale"].dtype == jnp.float32
        assert stored["layers"]["ln2_post"]["scale"].shape == (L, 64)


def test_a_looped_expert_models_load_has_a_row_for_every_pass_and_layer():
    cfg = dataclasses.replace(CFG, qk_norm=True, num_experts=4,
                              experts_per_token=2)
    p = llama.llama_init(jax.random.PRNGKey(0), cfg)
    _, kp, vp, load = prefill(p, cfg)
    assert load.shape == (T * L, 4)
    assert (np.asarray(load).sum(axis=1) == LENGTH * 2).all()


def test_llama_loss_refuses_a_looped_model(params):
    batch = {"tokens": jnp.zeros((2, 9), jnp.int32)}
    with pytest.raises(NotImplementedError, match="exit steps"):
        llama.llama_loss(params, batch, CFG)
    one_pass = dataclasses.replace(CFG, ut_steps=1)
    assert np.isfinite(float(llama.llama_loss(params, batch, one_pass)))
