"""The dropless expert path (``ops/moe.py::moe_dropless``) and its callers in
``models/llama.py`` and the serving engine, at tiny widths on the CPU.

Every assignment of a real token is computed whatever the load; a real
token's result does not depend on what padding or idle slots hold (which are
routed to no expert: ``tests/test_moe_dead_rows.py``); and what the engine
counts of the routing agrees with a count made here in numpy.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (LlamaConfig, llama_decode_step,
                                  llama_forward, llama_init,
                                  llama_init_paged_cache, llama_loss,
                                  llama_param_axes, llama_prefill)
from ray_tpu.ops.moe import moe_dropless, moe_router

D, M, E, K = 32, 16, 8, 3
CFG = LlamaConfig(vocab_size=97, max_seq_len=48, num_layers=2, num_heads=4,
                  num_kv_heads=4, embed_dim=D, mlp_dim=M, num_experts=E,
                  experts_per_token=K, qk_norm=True, dtype=jnp.float32,
                  attention="dense", remat=False)
PAGE, PROMPT, BATCH = 8, 16, 4
MAXP = CFG.max_seq_len // PAGE
PREFILL = jax.jit(lambda p, *a: llama_prefill(p, CFG, *a))
DECODE = jax.jit(lambda p, *a: llama_decode_step(p, CFG, *a))


def experts_params(key, experts=E):
    k = jax.random.split(key, 3)
    return {"router": jax.random.normal(k[0], (D, experts), jnp.float32),
            "wgu": 0.3 * jax.random.normal(k[1], (experts, 2, D, M),
                                           jnp.float32),
            "wd": 0.3 * jax.random.normal(k[2], (experts, M, D),
                                          jnp.float32)}


def all_experts(x, p, top_k, renorm=False):
    """Every expert on every token, weighed by a [T, E] gate matrix."""
    x, p = np.asarray(x, np.float64), jax.tree.map(
        lambda a: np.asarray(a, np.float64), p)
    logits = x @ p["router"]
    probs = np.exp(logits - logits.max(-1, keepdims=True))
    probs /= probs.sum(-1, keepdims=True)
    kth = np.sort(probs, -1)[:, -top_k][:, None]
    gates = np.where(probs >= kth, probs, 0.0)
    if renorm:
        gates /= gates.sum(-1, keepdims=True)
    gate = np.einsum("td,edm->tem", x, p["wgu"][:, 0])
    up = np.einsum("td,edm->tem", x, p["wgu"][:, 1])
    each = np.einsum("tem,emd->ted", gate / (1 + np.exp(-gate)) * up,
                     p["wd"])
    return np.einsum("ted,te->td", each, gates), gates > 0


@pytest.mark.parametrize("tokens", [1, 16, 67])
@pytest.mark.parametrize("renorm", [False, True])
def test_dropless_equals_every_expert_on_every_token(tokens, renorm):
    p = experts_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, D))
    y, load = moe_dropless(x, p, top_k=K, norm_topk_prob=renorm)
    want, chosen = all_experts(x, p, K, renorm)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(load), chosen.sum(0))
    assert int(load.sum()) == tokens * K


def test_an_expert_with_eight_times_the_mean_load_drops_nothing():
    """A router that sends every token to expert 0 first: of 16 experts at
    2 a token it gets all 64 tokens where the mean load is 64 * 2 / 16 = 8,
    and 54 more than the capacity path (factor 1.25: 10 slots) keeps."""
    tokens, experts, top_k = 64, 16, 2
    p = experts_params(jax.random.PRNGKey(1), experts)
    x = jax.random.normal(jax.random.PRNGKey(2), (tokens, D))
    x = x.at[:, 0].set(3.0)            # the direction the skew rides on
    p["router"] = p["router"].at[0, 0].add(10.0)
    y, load = moe_dropless(x, p, top_k=top_k)
    want, chosen = all_experts(x, p, top_k)
    load = np.asarray(load)
    assert load[0] == tokens == 8 * load.mean()
    assert load.sum() == tokens * top_k
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    # the capacity path, on the same routing, loses tokens of expert 0
    capacity = int(1.25 * tokens * top_k / experts)
    dispatch, _, _ = moe_router(x[None], p["router"], top_k=top_k,
                                capacity=capacity)
    assert float(dispatch[0, :, 0].sum()) == capacity < tokens
    assert float(dispatch.sum()) < tokens * top_k


def test_live_selects_what_is_counted_and_nothing_else():
    """A live row is the same bits with ``live`` and without; the routed part
    of a dead row is exactly zero; ``load`` counts the live rows' alone."""
    p = experts_params(jax.random.PRNGKey(3))
    x = jax.random.normal(jax.random.PRNGKey(4), (12, D))
    live = jnp.arange(12) % 3 != 0
    y_all, load_all = moe_dropless(x, p, top_k=K)
    y, load = moe_dropless(x, p, top_k=K, live=live)
    alive = np.asarray(live)
    np.testing.assert_array_equal(np.asarray(y)[alive],
                                  np.asarray(y_all)[alive])
    np.testing.assert_array_equal(np.asarray(y)[~alive], 0.0)
    assert float(jnp.abs(y_all[~alive]).min()) > 0
    _, chosen = all_experts(x, p, K)
    np.testing.assert_array_equal(np.asarray(load), chosen[alive].sum(0))
    assert int(load.sum()) == int(live.sum()) * K < int(load_all.sum())


# ---------------------------------------------------------------- the model

@pytest.fixture(scope="module")
def params():
    p = llama_init(jax.random.PRNGKey(0), CFG)
    # the init's router is nearly flat and its norms are the identity
    p["layers"]["mlp"]["router"] = p["layers"]["mlp"]["router"] * 30
    for name, seed in (("q_norm", 5), ("k_norm", 6)):
        scale = p["layers"]["attn"][name]
        p["layers"]["attn"][name] = 1 + 0.2 * jax.random.normal(
            jax.random.PRNGKey(seed), scale.shape)
    return p


def pools():
    return llama_init_paged_cache(CFG, BATCH * MAXP + 1, PAGE)


def table(rows):
    t = np.zeros((rows, MAXP), np.int32)
    t[0] = np.arange(1, MAXP + 1)
    return t


def test_init_and_axes_have_the_same_leaves(params):
    axes = llama_param_axes(CFG)
    assert jax.tree.structure(jax.tree.map(lambda a: 0, params)) == \
        jax.tree.structure(jax.tree.map(
            lambda a: 0, axes, is_leaf=lambda a: isinstance(a, tuple)))
    mlp = params["layers"]["mlp"]
    assert mlp["wgu"].shape == (2, E, 2, D, M)
    assert mlp["wd"].shape == (2, E, M, D)
    assert mlp["router"].shape == (2, D, E)
    assert axes["layers"]["mlp"]["wgu"][1] == "expert"
    assert params["layers"]["attn"]["q_norm"].shape == (2, 4, D // 4)
    dense = llama_init(jax.random.PRNGKey(0), LlamaConfig.tiny())
    assert set(dense["layers"]["mlp"]) == {"wgu", "wd"}
    assert set(dense["layers"]["attn"]) == {"wq", "wkv", "wo"}


def test_the_expert_model_is_not_trained_without_its_auxiliary_loss(params):
    with pytest.raises(NotImplementedError, match="load-balancing"):
        llama_loss(params, {"tokens": jnp.zeros((1, 9), jnp.int32)}, CFG)
    with pytest.raises(ValueError, match="experts_per_token"):
        llama_init(jax.random.PRNGKey(0),
                   dataclasses.replace(CFG, experts_per_token=E + 1))


@pytest.mark.parametrize("filler", [0, 13, 96])
def test_prefill_of_real_tokens_ignores_the_padding(params, filler):
    """Logits, the cached keys and values and the load of the prompt's real
    positions are the same whatever the padded positions hold."""
    prompt = np.arange(3, 14, dtype=np.int32)
    kp, vp = pools()

    def run(fill):
        toks = np.full((1, PROMPT), fill, np.int32)
        toks[0, :len(prompt)] = prompt
        return PREFILL(params, toks, np.int32(len(prompt)), kp,
                             vp, table(1))
    logits, k1, v1, load = run(filler)
    base_logits, k0, v0, base_load = run(1)
    np.testing.assert_array_equal(np.asarray(logits),
                                  np.asarray(base_logits))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(base_load))
    np.testing.assert_array_equal(np.asarray(k1[:, :, 1:2]),
                                  np.asarray(k0[:, :, 1:2]))
    assert load.shape == (2, E)
    assert np.asarray(load).sum(1).tolist() == [len(prompt) * K] * 2
    full = llama_forward(params, jnp.asarray(prompt)[None], CFG)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full[0, -1]), rtol=2e-4,
                               atol=2e-5)


@pytest.mark.parametrize("idle_token", [0, 50])
def test_decode_of_a_live_slot_ignores_the_idle_slots(params, idle_token):
    """One live sequence among idle slots (position 0, scratch page 0):
    its logits and the load are the same whatever token the idle slots
    churn, and the load is that sequence's K experts a layer."""
    prompt = np.arange(3, 14, dtype=np.int32)
    kp, vp = pools()
    toks = np.zeros((1, PROMPT), np.int32)
    toks[0, :len(prompt)] = prompt
    _, kp, vp, _ = PREFILL(params, toks, np.int32(len(prompt)), kp, vp,
                           table(1))

    def step(idle):
        token = np.full((BATCH,), idle, np.int32)
        pos = np.zeros((BATCH,), np.int32)
        token[0], pos[0] = 21, len(prompt)
        return DECODE(params, token, pos, kp, vp, table(BATCH))
    logits, _, _, load = step(idle_token)
    base_logits, _, _, base_load = step(7)
    np.testing.assert_array_equal(np.asarray(logits[0]),
                                  np.asarray(base_logits[0]))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(base_load))
    load = np.asarray(load)
    assert load.shape == (2, E) and set(load.ravel()) == {0, 1}
    assert load.sum(1).tolist() == [K, K]
    full = llama_forward(
        params, jnp.asarray(np.append(prompt, 21))[None], CFG)
    np.testing.assert_allclose(np.asarray(logits[0]),
                               np.asarray(full[0, -1]), rtol=2e-4,
                               atol=2e-5)


# --------------------------------------------------------------- the engine

def routed_experts(params, tokens):
    """[L, S, E] bool: each position's experts, from a forward pass written
    here around the model's own attention (numpy routing on its hiddens)."""
    from ray_tpu.models import llama
    cos, sin = llama.rope_tables(len(tokens), CFG.head_dim, CFG.rope_theta)
    x = params["wte"][jnp.asarray(tokens)][None]
    out = []
    for i in range(CFG.num_layers):
        p = jax.tree.map(lambda a: a[i], params["layers"])
        h = llama._rms_norm(x, p["ln1"]["scale"], CFG.rms_eps)
        q = jnp.einsum("bsd,dnh->bnsh", h, p["attn"]["wq"])
        kv = jnp.einsum("bsd,dcnh->bcnsh", h, p["attn"]["wkv"])
        q, k = llama._qk(CFG, p, q, kv[:, 0], cos, sin)
        o = llama._dense_causal_attention_gqa(q, k, kv[:, 1], 1)
        x = x + jnp.einsum("bnsh,nhd->bsd", o, p["attn"]["wo"])
        h = llama._rms_norm(x, p["ln2"]["scale"], CFG.rms_eps)
        y, chosen = all_experts(h[0], p["mlp"], K)
        out.append(chosen)
        x = x + jnp.asarray(y, jnp.float32)[None]
    return np.stack(out)


def test_engine_counts_hits_and_loads_as_numpy_does(params):
    """Two sequences through the engine, one after the other so that every
    step's live tokens are known: its ``moe_*`` counters equal a count of
    the experts a forward pass over the generated text chooses."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    config = EngineConfig(model="llama", model_config=CFG, page_size=PAGE,
                          num_pages=BATCH * MAXP + 1, max_batch=BATCH,
                          max_prompt_len=PROMPT, max_new_tokens=8)
    prompts, new = ([5, 17, 3, 88, 41, 2, 9], [7, 8, 9]), (6, 4)

    async def go():
        engine = InferenceEngine(config, params=params)
        tokens = [[t async for t in engine.generate(p, n)]
                  for p, n in zip(prompts, new)]
        stats = engine.stats()
        engine.close()
        return tokens, stats

    tokens, stats = asyncio.run(go())
    want = {"moe_assignments": 0, "moe_experts_hit": 0, "moe_load_max": 0}
    for prompt, generated in zip(prompts, tokens):
        # the last generated token is never fed back
        chosen = routed_experts(params, prompt + generated[:-1])
        steps = [chosen[:, :len(prompt)]] + [
            chosen[:, i:i + 1] for i in range(len(prompt), chosen.shape[1])]
        for step in steps:                     # [L, live tokens, E]
            load = step.sum(1)
            want["moe_assignments"] += int(load.sum())
            want["moe_experts_hit"] += int((load > 0).sum())
            want["moe_load_max"] += int(load.max(1).sum())
    assert {k: stats[k] for k in want} == want
    assert stats["moe_assignments"] == sum(
        (len(p) + n - 1) * K * CFG.num_layers for p, n in zip(prompts, new))
    assert stats["steps"] == sum(new) - len(new)


def test_a_dense_model_counts_nothing():
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    config = EngineConfig(model="llama", page_size=PAGE, num_pages=16,
                          max_batch=2, max_prompt_len=PROMPT,
                          max_new_tokens=4)

    async def go():
        engine = InferenceEngine(config)
        tokens = [t async for t in engine.generate([1, 2, 3], 3)]
        stats = engine.stats()
        engine.close()
        return tokens, stats

    tokens, stats = asyncio.run(go())
    assert len(tokens) == 3
    assert (stats["moe_assignments"], stats["moe_experts_hit"],
            stats["moe_load_max"]) == (0, 0, 0)


# ------------------------------------------- sigmoid routing, shared expert

def sigmoid_experts(x, p, top_k, renorm, scale, shared=None):
    """Every expert on every token under DeepSeek-V3's ``noaux_tc`` routing,
    in float64: sigmoid scores, the ``top_k`` largest of score + bias chosen,
    gates the chosen scores WITHOUT the bias (renormalised, scaled), and the
    shared expert's output added ungated."""
    x, p = np.asarray(x, np.float64), jax.tree.map(
        lambda a: np.asarray(a, np.float64), p)
    scores = 1 / (1 + np.exp(-(x @ p["router"])))
    biased = scores + p.get("router_bias", 0.0)
    kth = np.sort(biased, -1)[:, -top_k][:, None]
    gates = np.where(biased >= kth, scores, 0.0)
    if renorm:
        gates /= gates.sum(-1, keepdims=True) + 1e-20
    gates *= scale

    def swiglu(wgu, wd):
        gate, up = x @ wgu[0], x @ wgu[1]
        return (gate / (1 + np.exp(-gate)) * up) @ wd
    each = np.stack([swiglu(g, d) for g, d in zip(p["wgu"], p["wd"])], 1)
    y = np.einsum("ted,te->td", each, gates)
    if shared is not None:
        y = y + swiglu(*(np.asarray(shared[k], np.float64)
                         for k in ("wgu", "wd")))
    return y, gates > 0


@pytest.mark.parametrize("renorm,scale,bias,shared", [
    (True, 2.0, True, True), (False, 1.0, True, False),
    (True, 1.0, False, True), (False, 2.5, False, False)])
def test_sigmoid_routing_with_bias_and_a_shared_expert(renorm, scale, bias,
                                                       shared):
    p = experts_params(jax.random.PRNGKey(20))
    if bias:     # large enough to change which experts are chosen
        p["router_bias"] = 0.3 * jax.random.normal(jax.random.PRNGKey(21),
                                                   (E,))
    extra = {"wgu": 0.3 * jax.random.normal(jax.random.PRNGKey(22),
                                            (2, D, 2 * M)),
             "wd": 0.3 * jax.random.normal(jax.random.PRNGKey(23),
                                           (2 * M, D))} if shared else None
    x = jax.random.normal(jax.random.PRNGKey(24), (23, D))
    y, load = moe_dropless(x, p, top_k=K, norm_topk_prob=renorm,
                           scoring="sigmoid", routed_scaling=scale,
                           shared=extra)
    want, chosen = sigmoid_experts(x, p, K, renorm, scale, extra)
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(load), chosen.sum(0))
    if bias:     # the bias selects, and is not in the gates
        unbiased = {k: v for k, v in p.items() if k != "router_bias"}
        _, plain = sigmoid_experts(x, unbiased, K, renorm, scale, extra)
        assert (plain != chosen).any()
    if renorm:   # the gates of a token sum to the scaling factor
        _, load_one = moe_dropless(x[:1], p, top_k=K, scoring="sigmoid")
        assert int(load_one.sum()) == K


def kernel_calls(jaxpr):
    """The ``pallas_call`` equations of a jaxpr, nested ones included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += kernel_calls(sub)
    return found


def test_bfloat16_experts_are_multiplied_as_they_are_stored():
    """Stored in bfloat16 the experts are read in bfloat16 (the activations
    cast to them, never the experts to the activations), and the result is
    that of the rounded experts to bfloat16's precision."""
    p = experts_params(jax.random.PRNGKey(30))
    stored = {**p, "wgu": p["wgu"].astype(jnp.bfloat16),
              "wd": p["wd"].astype(jnp.bfloat16)}
    x = jax.random.normal(jax.random.PRNGKey(31), (19, D))
    jaxpr = jax.make_jaxpr(
        lambda x, p: moe_dropless(x, p, top_k=K))(x, stored)
    assert "ragged_dot" not in str(jaxpr)
    calls = kernel_calls(jaxpr.jaxpr)
    assert len(calls) == 2
    for call, weights in zip(calls, ((2 * E, D, M), (E, M, D))):
        # the rows, the experts as they lie in the stack, the result
        rows, experts = call.invars[-2:]
        assert experts.aval.shape == weights
        assert {rows.aval.dtype, experts.aval.dtype,
                call.outvars[0].aval.dtype} == {jnp.dtype(jnp.bfloat16)}
    y, load = moe_dropless(x, stored, top_k=K)
    assert y.dtype == x.dtype
    rounded = jax.tree.map(lambda a: a.astype(jnp.float32), stored)
    want, chosen = all_experts(x, rounded, K)
    assert np.linalg.norm(np.asarray(y) - want) / np.linalg.norm(want) < 2e-2
    np.testing.assert_array_equal(np.asarray(load), chosen.sum(0))


# ------------------------------------- the grouped matmul kernel in a stack

LAYERS = 3
IN_STACK = jax.jit(lambda x, p, layer, top_k: moe_dropless(
    x, p, top_k=top_k, layer=layer), static_argnums=3)


def stack_around(p, layer):
    """A stack of LAYERS in which only ``layer`` holds ``p``: every other
    layer's router and experts are NaN, so a read of a wrong layer shows."""
    return {k: jnp.stack([v if i == layer else jnp.full_like(v, jnp.nan)
                          for i in range(LAYERS)]) for k, v in p.items()}


@pytest.mark.parametrize("stored", ["float32", "bfloat16"])
@pytest.mark.parametrize("tokens", [1, 19, 67])
def test_a_traced_layer_reads_its_own_experts_in_the_stack(tokens, stored):
    """``layer`` traced (one program for the three), picking each layer of
    the stack in turn: 3, 57 and 201 assignments, so row counts that no tile
    divides, and with 67 tokens the gate/up rows (402) span four row tiles
    which groups share."""
    x = jax.random.normal(jax.random.PRNGKey(tokens), (tokens, D))
    programs = set()
    for layer in range(LAYERS):
        p = experts_params(jax.random.PRNGKey(40 + layer))
        p = {**p, "wgu": p["wgu"].astype(stored),
             "wd": p["wd"].astype(stored)}
        y, load = IN_STACK(x, stack_around(p, layer), jnp.int32(layer), K)
        programs.add(IN_STACK._cache_size())
        want, chosen = all_experts(x, p, K)
        np.testing.assert_array_equal(np.asarray(load), chosen.sum(0))
        if stored == "float32":
            np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4,
                                       atol=1e-5)
        else:
            assert np.linalg.norm(np.asarray(y) - want) \
                < 2e-2 * np.linalg.norm(want)
    assert len(programs) == 1


@pytest.mark.parametrize("layer", range(LAYERS))
def test_an_expert_with_every_row_and_experts_with_none_in_the_stack(layer):
    """Of 16 experts at 2 a token, expert 0 is every token's first choice
    and the last eight are nobody's: one group holds half the rows (two row
    tiles of the gate/up matmul and more), eight groups hold none and get
    no visit, and the layers around are NaN."""
    tokens, experts, top_k = 96, 16, 2
    p = experts_params(jax.random.PRNGKey(50), experts)
    x = jax.random.normal(jax.random.PRNGKey(51), (tokens, D))
    x = x.at[:, 0].set(3.0)
    p["router"] = p["router"].at[0, 0].add(10.0).at[0, 8:].add(-10.0)
    y, load = IN_STACK(x, stack_around(p, layer), jnp.int32(layer), top_k)
    want, chosen = all_experts(x, p, top_k)
    load = np.asarray(load)
    assert load[0] == tokens and not load[8:].any()
    np.testing.assert_array_equal(load, chosen.sum(0))
    np.testing.assert_allclose(np.asarray(y), want, rtol=1e-4, atol=1e-5)


def groups_product(lhs, rhs, sizes):
    """Each group's rows times its matrix, in float64."""
    out, at = [], 0
    for g, n in enumerate(sizes):
        out.append(np.asarray(lhs[at:at + n], np.float64)
                   @ np.asarray(rhs[g], np.float64))
        at += n
    return np.concatenate(out)


@pytest.mark.parametrize("sizes", [
    [5, 0, 130, 1, 0, 64], [0, 0, 0, 0, 0, 7], [128, 128, 0, 0, 1, 0],
    [300, 0, 0, 0, 0, 0]], ids=["ragged", "last-only", "whole-tiles", "one"])
@pytest.mark.parametrize("tile_bytes", [None, 128 * 128 * 4])
def test_grouped_matmul_against_each_groups_product(monkeypatch, sizes,
                                                    tile_bytes):
    """The kernel alone: groups that end inside a row tile, fill whole ones
    or have no rows, the second layer of a stack of three, and (with the
    weights' tile held to [128, 128]) N walked in two tiles."""
    from ray_tpu.ops import grouped_matmul as gm
    if tile_bytes:
        monkeypatch.setattr(gm, "_WEIGHT_TILE_BYTES", tile_bytes)
    G, Kd, N, m = len(sizes), 128, 256, sum(sizes)
    rhs = jax.random.normal(jax.random.PRNGKey(60), (3 * G, Kd, N))
    rhs = rhs.at[:G].set(jnp.nan).at[2 * G:].set(jnp.nan)
    lhs = jax.random.normal(jax.random.PRNGKey(61), (m, Kd))
    assert gm._tiles(m, Kd, N, 4)[1] == (128 if tile_bytes else 256)
    out = gm.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32),
                            jnp.int32(1))
    assert out.shape == (m, N) and out.dtype == lhs.dtype
    np.testing.assert_allclose(np.asarray(out),
                               groups_product(lhs, rhs[G:2 * G], sizes),
                               rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("case,tiles", [
    # rows, K, N, bytes a parameter -> (row tile, tile of N)
    ("sdar gate/up", ((2048, 2048, 768, 2), (128, 768))),
    ("sdar down", ((1024, 768, 2048, 2), (128, 2048))),
    ("xing gate/up", ((256, 3584, 1024, 2), (128, 1024))),
    ("xing down", ((128, 1024, 3584, 2), (128, 3584))),
    ("olmoe gate/up", ((256, 2048, 1024, 4), (128, 1024))),
    ("olmoe down", ((128, 1024, 2048, 4), (128, 2048))),
    ("olmoe prefill 512", ((8192, 2048, 1024, 4), (128, 1024))),
    ("sdar prefill 2048", ((32768, 2048, 768, 2), (128, 768))),
    ("a wide float32 expert", ((64, 4096, 4096, 4), (64, 512))),
    # the row tile does not grow with the rows (it was 512 here)
    ("many rows a group", ((65536, 2048, 1024, 2), (128, 1024))),
    ("lfm2 gate/up at rung 2048", ((16384, 2048, 1536, 2), (128, 1536))),
    ("lfm2 down at rung 2048", ((8192, 1536, 2048, 2), (128, 2048))),
    ("lfm2 gate/up at rung 4096", ((32768, 2048, 1536, 2), (128, 1536))),
    ("lfm2 down at rung 4096", ((16384, 1536, 2048, 2), (128, 2048))),
    ("one token", ((3, 32, 16, 4), (8, 16))),
    ("one token, bfloat16", ((3, 32, 16, 2), (16, 16)))])
def test_tiles_follow_from_the_shapes(case, tiles):
    from ray_tpu.ops import grouped_matmul as gm
    shapes, want = tiles
    assert gm._tiles(*shapes) == want, case
    tm, tn = want
    _, Kd, N, itemsize = shapes
    assert N % tn == 0
    # two buffers each of the weights' tile, the rows and the result, and
    # the float32 product, inside what the call asks for
    assert 2 * itemsize * (Kd * tn + tm * Kd + tm * tn) + 4 * tm * tn \
        < gm._VMEM_LIMIT_BYTES
