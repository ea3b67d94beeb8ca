"""SDAR at a tiny size on the CPU, float32: the program's prefill and block
steps through the paged cache against ``benchmark/reference/sdar.py``'s full
forward, the engine's streamed tokens against the reference's published
generation loop, the faults the replica's check has to catch, and what the
program and the family refuse."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import tiny_sdar
from test_tools_sdar import tool
from benchmark import replica_blocks, spec
from benchmark.reference import sdar as reference
from ray_tpu.models import llama
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

B = 4
LOOPS = {}


def build(config=tiny_sdar.TINY_SDAR, params=None, model=None, **overrides):
    family, made, tree = tiny_sdar.program(config, **overrides)
    engine = InferenceEngine(EngineConfig(
        model="llama", model_config=model or made, **config["engine"]),
        params=tree if params is None else params)
    return family, made, tree, engine


def prompt_of(length, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, 255, length)]


def served_against_reference(config, engine, params, family, lengths=(7, 14)):
    """Relative error of each pass's logits, by ``replica_blocks.drive``."""
    errs = []
    run = jax.jit(lambda p, t: family.reference_forward(p, t, config))
    for n, length in enumerate(lengths):
        tokens = np.asarray(prompt_of(length // B * B + 3 * B, n), np.int32)
        for stood, logits, pos0 in replica_blocks.drive(
                engine, engine._params, tokens, length, 255):
            want = np.asarray(run(params, jnp.asarray(stood)))[pos0:pos0 + B]
            errs.append(float(np.linalg.norm(logits - want)
                              / np.linalg.norm(want)))
    return errs


# ------------------------------------------------ the reference by itself

def test_the_reference_is_causal_over_blocks_and_two_way_inside_one():
    family, model, params = tiny_sdar.program()
    config = tiny_sdar.TINY_SDAR
    tokens = np.asarray(prompt_of(16), np.int32)
    base = np.asarray(family.reference_forward(params, tokens, config))
    later = tokens.copy()
    later[9] = (later[9] + 1) % 255          # in the third block
    moved = np.asarray(family.reference_forward(params, later, config))
    np.testing.assert_array_equal(moved[:8], base[:8])      # blocks before
    # the block's FIRST row sees the change behind it: both ways inside
    assert np.abs(moved[8] - base[8]).max() > 1e-4
    assert np.abs(moved[12:] - base[12:]).max() > 1e-4      # and after


def test_the_reference_reads_each_head_by_itself_in_the_norm():
    """Scaling one head's query projection changes nothing: the norm is over
    that head's values alone.  Pooled over all heads it would."""
    family, model, params = tiny_sdar.program()
    config = tiny_sdar.TINY_SDAR
    tokens = np.asarray(prompt_of(8), np.int32)
    attn = params["layers"]["attn"]
    scaled = {**params, "layers": {**params["layers"], "attn": {
        **attn, "wq": attn["wq"].at[:, :, 1].multiply(3.0)}}}
    a = np.asarray(family.reference_forward(params, tokens, config))
    b = np.asarray(family.reference_forward(scaled, tokens, config))
    np.testing.assert_allclose(a, b, rtol=0, atol=2e-4)
    pooled = np.asarray(family.reference_forward(
        {**scaled, "layers": {**scaled["layers"], "attn": {
            **scaled["layers"]["attn"],
            "wkv": attn["wkv"].at[:, :, 0, 0].multiply(3.0)}}},
        tokens, config))
    np.testing.assert_allclose(a, pooled, rtol=0, atol=2e-4)   # a KV head too


@pytest.mark.parametrize("steps,counts", [(4, [1, 1, 1, 1]), (2, [2, 2]),
                                          (3, [2, 1, 1]), (1, [4])])
def test_transfer_counts_sum_to_the_block(steps, counts):
    assert reference.transfer_counts(4, steps) == counts


def test_unmask_by_count_and_by_threshold():
    logits = np.log(np.asarray([[0.7, 0.2, 0.1], [0.4, 0.35, 0.25],
                                [0.9, 0.05, 0.05], [0.5, 0.3, 0.2]]))
    masked = np.asarray([True, True, False, True])
    x0, now = reference.unmask(logits, masked, 1, 0.0)
    assert list(x0) == [0, 0, 0, 0] and list(now) == [1, 0, 0, 0]
    _, now = reference.unmask(logits, masked, 1, 0.45)     # 0.7 and 0.5
    assert list(now) == [1, 0, 0, 1]
    _, now = reference.unmask(logits, masked, 1, 0.8)      # none: the count
    assert list(now) == [1, 0, 0, 0]
    _, now = reference.unmask(logits, masked, 5, 0.0)      # all that is left
    assert list(now) == [1, 1, 0, 1]


# ------------------------------------- the program against the reference

@pytest.fixture(scope="module")
def honest():
    family, model, params, engine = build()
    yield family, model, params, engine
    engine.close()


def test_prefill_and_blocks_through_the_pages_equal_the_forward(honest):
    family, model, params, engine = honest
    errs = served_against_reference(tiny_sdar.TINY_SDAR, engine, params,
                                    family)
    assert len(errs) == 18 and max(errs) < 1e-5, errs


def test_with_the_routing_code_too():
    family, model, params, engine = build(routing_code=True)
    try:
        errs = served_against_reference(tiny_sdar.TINY_SDAR, engine, params,
                                        family)
    finally:
        engine.close()
    assert max(errs) < 1e-5, errs


@pytest.mark.parametrize("fault", [
    "last denoise pass's K/V kept at a commit",
    "causal mask inside the block", "q/k norm pooled over all heads",
    "no q/k norm", "top-7", "logits shifted by one"])
def test_a_planted_fault_fails_the_comparison(fault):
    numerics = tool("numerics_sdar")
    planted = numerics.FAULTS[fault]
    family, model, params = tiny_sdar.program()
    if "config" in planted:
        model = dataclasses.replace(model, **planted["config"](model))
    with numerics.planted(planted):
        engine = InferenceEngine(EngineConfig(
            model="llama", model_config=model,
            **tiny_sdar.TINY_SDAR["engine"]), params=params)
        try:
            errs = served_against_reference(tiny_sdar.TINY_SDAR, engine,
                                            params, family)
        finally:
            engine.close()
    assert max(errs) > 1e-2, errs
    if fault.startswith("last denoise"):
        # the first block's passes read only what the prefill left: honest
        assert max(errs[:3]) < 1e-5 and min(errs[3:9]) > 1e-3, errs


def generate_all(engine, asked):
    async def run():
        async def one(p, n):
            return [t async for t in engine.generate(p, n)]
        return await asyncio.gather(*(one(p, n) for p, n in asked))
    # one loop an engine, left open: the engine's task lives on it
    loop = LOOPS.setdefault(id(engine), asyncio.new_event_loop())
    return loop.run_until_complete(run())


# prompt lengths with len % B in {0, 1, B - 1}, one under a block; answers
# that are and are not whole blocks
ASKED = [(prompt_of(12, 1), 9), (prompt_of(13, 2), 8), (prompt_of(15, 3), 16),
         (prompt_of(3, 4), 6), (prompt_of(8, 5), 1), (prompt_of(17, 6), 3)]


@pytest.mark.parametrize("changes", [
    {}, {"denoising_steps": 2}, {"denoising_steps": 3},
    {"confidence_threshold": 0.006}], ids=str)
def test_the_engine_streams_the_references_tokens(changes):
    config = tiny_sdar.with_generation(tiny_sdar.TINY_SDAR, **changes)
    family, model, params, engine = build(config)
    try:
        got = generate_all(engine, ASKED)
        stats = engine.stats()
    finally:
        engine.close()
    passes = []
    for (prompt, n), tokens in zip(ASKED, got):
        want, taken = family.reference_generate(params, prompt, n, config,
                                                with_passes=True)
        assert tokens == want and len(tokens) == n
        passes += taken
    block = stats["block"]
    assert block["blocks_committed"] == len(passes)
    assert block["denoise_passes_by_count"] == {
        t: passes.count(t) for t in range(1, model.denoise_steps + 1)}
    assert block["slot_steps_denoise"] == sum(passes)
    assert len({t for tokens in got for t in tokens}) > 8    # no one token
    if changes.get("confidence_threshold"):
        assert block["unmasked_by_threshold"] > 0
        assert sum(passes) < 4 * len(passes)     # it fired: fewer passes
    else:
        assert block["unmasked_by_threshold"] == 0


def test_a_prompt_or_an_argmax_may_be_the_mask_token(honest):
    """Masks are a boolean beside the tokens: a prompt full of the mask
    token's id is a prompt, and generation ends."""
    family, model, params, engine = honest
    prompt = [model.mask_token] * 10
    (got,) = generate_all(engine, [(prompt, 7)])
    assert got == family.reference_generate(params, prompt, 7,
                                            tiny_sdar.TINY_SDAR)


# ------------------------------------------------------------ refusals

def test_check_refuses_what_is_not_written():
    base = dict(vocab_size=64, block_length=4, denoise_steps=4, mask_token=63)
    llama._check(llama.LlamaConfig(**base))
    for bad, said in [
            ({"denoise_steps": 0}, "denoise_steps"),
            ({"denoise_steps": 5}, "denoise_steps"),
            ({"mask_token": 64}, "mask_token"),
            ({"confidence_threshold": 1.0}, "confidence_threshold"),
            ({"ut_steps": 2}, "block_length"),
            ({"hc_mult": 4}, "block_length"),
            ({"qk_norm": True, "qk_norm_per_head": True}, "qk_norm")]:
        with pytest.raises(ValueError, match=said):
            llama._check(llama.LlamaConfig(**{**base, **bad}))
    with pytest.raises(ValueError, match="block_length"):
        llama._check(llama.LlamaConfig(denoise_steps=2))
    with pytest.raises(ValueError, match="page_size"):
        llama.llama_init_paged_cache(llama.LlamaConfig(**base), 9, 6)
    with pytest.raises(NotImplementedError, match="block"):
        llama.llama_hidden({}, jnp.zeros((1, 8), jnp.int32),
                           llama.LlamaConfig(**base))


def test_latent_attention_refuses_blocks_and_a_head_size():
    xing = spec.load_part("families", "xing").program_config(
        spec.load_json("configs", "xing4.0-29b-a4b-6l.json"), 64)
    for bad in ({"block_length": 4, "denoise_steps": 4}, {"head_size": 128},
                {"qk_norm_per_head": True}):
        with pytest.raises(ValueError):
            llama._check(dataclasses.replace(xing, **bad))


@pytest.mark.parametrize("key,value", [
    ("attention_bias", True), ("mlp_only_layers", [0]),
    ("decoder_sparse_step", 2), ("sliding_window", 4096),
    ("norm_topk_prob", False), ("tie_word_embeddings", True)])
def test_the_family_refuses_what_the_program_does_not_run(key, value):
    family = spec.load_part("families", "sdar")
    with pytest.raises(ValueError, match=key):
        family.program_config({**tiny_sdar.TINY_SDAR, key: value}, 48)


def test_the_family_refuses_another_remasking_rule():
    family = spec.load_part("families", "sdar")
    config = tiny_sdar.with_generation(tiny_sdar.TINY_SDAR,
                                       remasking="random")
    with pytest.raises(ValueError, match="random"):
        family.program_config(config, 48)
    static = tiny_sdar.with_generation(tiny_sdar.TINY_SDAR,
                                       remasking="low_confidence_static")
    assert family.program_config(static, 48).confidence_threshold == 0.0


def test_the_head_width_is_the_published_one():
    family = spec.load_part("families", "sdar")
    model = family.program_config(tiny_sdar.TINY_SDAR, 48)
    assert model.head_dim == 24 != model.embed_dim // model.num_heads
    shapes = jax.eval_shape(lambda: family.init(jax.random.PRNGKey(0), model))
    attn = shapes["layers"]["attn"]
    assert attn["wq"].shape == (2, 64, 4, 24)
    assert attn["q_norm"].shape == attn["k_norm"].shape == (2, 24)
    assert shapes["layers"]["mlp"]["wgu"].dtype == jnp.bfloat16
    assert shapes["layers"]["mlp"]["router"].dtype == jnp.float32


def test_the_routing_code_is_what_the_family_says():
    """Every token names ``experts_per_token`` experts, every layer's router
    reads the code's places alone, no sublayer writes there, and the
    reference then gives every token exactly those experts at 1 / k."""
    family, model, params = tiny_sdar.program(routing_code=True)
    E, k = model.num_experts, model.experts_per_token
    code = np.asarray(params["wte"][:, :E], np.float32)
    assert ((code > 0).sum(1) == k).all()
    assert np.unique(code[code > 0]).size == 1
    router = np.asarray(params["layers"]["mlp"]["router"])
    assert (router[:, E:] == 0).all()
    assert ((router[:, :E] > 0).sum(1) == 1).all()      # a permutation
    assert ((router[:, :E] > 0).sum(2) == 1).all()
    assert (np.asarray(params["layers"]["attn"]["wo"],
                       np.float32)[..., :E] == 0).all()
    assert (np.asarray(params["layers"]["mlp"]["wd"],
                       np.float32)[..., :E] == 0).all()
    tokens = np.asarray(prompt_of(12), np.int32)
    _, gates = family.reference_forward(params, tokens,
                                        tiny_sdar.TINY_SDAR, with_gates=True)
    gates = np.asarray(gates)
    assert ((gates > 0).sum(-1) == k).all()
    np.testing.assert_allclose(gates[gates > 0], 1.0 / k, rtol=1e-6)
    for layer in range(gates.shape[0]):
        named = (code[tokens] > 0) @ (router[layer, :E] > 0)
        np.testing.assert_array_equal(named > 0, gates[layer] > 0)
