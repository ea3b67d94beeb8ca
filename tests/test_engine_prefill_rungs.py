"""A prefill padded to its prompt's rung (``serve/engine/engine.py``), at tiny
widths on the CPU.

The ladder follows from ``max_prompt_len`` and ``page_size`` alone; a prompt
prefilled at its rung gives the logits and the pages it gives at
``max_prompt_len`` (padding lies after the prompt under a causal mask and its
K/V go to scratch page 0), for every family the engine serves; the loop's
greedy tokens are those of an engine with the one top rung; every rung's
program is there before the first admission, so no later request compiles;
and the counters say which rungs ran.
"""

import asyncio
import concurrent.futures
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt import GPTConfig, gpt_init
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.engine import engine as engine_module
from ray_tpu.serve.engine.engine import prefill_rungs, rung_for

PAGE, PROMPT, NEW, BATCH = 8, 256, 8, 2
RUNGS = (128, 256)
LLAMA = LlamaConfig(vocab_size=97, max_seq_len=PROMPT + NEW, num_layers=2,
                    num_heads=4, num_kv_heads=2, embed_dim=32, mlp_dim=48,
                    attention="dense", remat=False, dtype=jnp.float32)
FAMILIES = {
    "llama-dense": ("llama", LLAMA, llama_init),
    "llama-experts": ("llama", dataclasses.replace(
        LLAMA, num_kv_heads=4, mlp_dim=16, num_experts=8,
        experts_per_token=3, qk_norm=True), llama_init),
    "llama-looped": ("llama", dataclasses.replace(
        LLAMA, ut_steps=3, post_norm=True), llama_init),
    "gpt": ("gpt", GPTConfig(vocab_size=97, max_seq_len=PROMPT + NEW,
                             num_layers=2, num_heads=4, embed_dim=32,
                             attention="dense", remat=False,
                             dtype=jnp.float32), gpt_init),
}


def build(family, max_prompt_len=PROMPT, page=PAGE):
    model, cfg, init = FAMILIES[family]
    cfg = dataclasses.replace(cfg, max_seq_len=max_prompt_len + NEW)
    return InferenceEngine(
        EngineConfig(model=model, model_config=cfg, page_size=page,
                     num_pages=BATCH * (max_prompt_len + NEW) // page + 1,
                     max_batch=BATCH, max_prompt_len=max_prompt_len,
                     max_new_tokens=NEW),
        params=init(jax.random.PRNGKey(3), cfg))


def prompt_of(n):
    return [int(t) for t in (np.arange(n) * 7 + 3) % 97]


def failing(real, calls=None):
    """In place of a rung's compiled program (``engine._rung_programs`` /
    ``engine._decode_programs``): a finished future holding a function that
    runs ``real``, so the pools it was given are consumed, and then raises."""
    def call(*args):
        if calls is not None:
            calls.append(1)
        real(*args)
        raise RuntimeError("the device fell over")
    fails = concurrent.futures.Future()
    fails.set_result(call)
    return fails


def serve(engine, prompts, new=NEW):
    """What ``generate()`` streams for each prompt, one after the other;
    once an engine, whose loop lives on the event loop of its first call."""
    async def run():
        return [[t async for t in engine.generate(p, new)] for p in prompts]
    return asyncio.run(run())


# ------------------------------------------------------------- the ladder

@pytest.mark.parametrize("max_prompt_len, page, want", [
    (64, 8, (64,)), (128, 16, (128,)), (512, 16, (128, 256, 512)),
    (2048, 16, (128, 256, 512, 1024, 2048)), (96, 16, (96,))])
def test_the_ladder_follows_from_the_prompt_limit_and_the_page(
        max_prompt_len, page, want):
    rungs = prefill_rungs(max_prompt_len, page)
    assert rungs == want
    assert rungs[-1] == max_prompt_len
    assert all(r % page == 0 for r in rungs)
    assert all(a < b for a, b in zip(rungs, rungs[1:]))
    # a prompt takes the least rung that holds it: every length, and so
    # every rung's edge from both sides
    for n in range(1, max_prompt_len + 1):
        assert rung_for(rungs, n) == min(r for r in rungs if r >= n)
    for below, rung in zip(rungs, rungs[1:]):
        assert rung_for(rungs, below) == below
        assert rung_for(rungs, below + 1) == rung


def test_a_page_that_does_not_divide_128_rounds_the_rungs_up_to_pages():
    assert prefill_rungs(480, 24) == (144, 264, 480)
    # a page wider than a rung: the rungs that round to the same are one
    assert prefill_rungs(2048, 512) == (512, 1024, 2048)


# ---------------------------------------- the rung's program is the top's

@pytest.mark.parametrize("family", FAMILIES)
def test_a_prompt_at_its_rung_gives_the_top_rungs_logits_and_pages(family):
    engine = build(family)
    try:
        assert engine._rungs == RUNGS
        n, rung = 77, RUNGS[0]          # 9 whole pages and 5 slots of a 10th
        table = np.zeros((1, engine._maxp), np.int32)
        table[0] = np.arange(1, engine._maxp + 1)
        got = {}
        for S in (rung, PROMPT):
            tokens = np.zeros((1, S), np.int32)
            tokens[0, :n] = prompt_of(n)
            logits, kp, vp, *load = engine._prefill_program(
                engine._params, tokens, np.int32(n), engine._k_pages,
                engine._v_pages, table)
            got[S] = [np.asarray(a) for a in (logits, kp, vp, *load)]
        for at_rung, at_top in zip(got[rung], got[PROMPT]):
            assert at_rung.shape == at_top.shape
        np.testing.assert_allclose(got[rung][0], got[PROMPT][0], rtol=0,
                                   atol=1e-5)
        assert np.abs(got[PROMPT][0]).max() > 1e-2
        used = -(-n // PAGE)
        for at_rung, at_top in zip(got[rung][1:3], got[PROMPT][1:3]):
            # [pool layers, pages, page, heads x head size]: the prompt's
            # pages hold the bits the top rung writes there
            np.testing.assert_array_equal(at_rung[:, 1:used + 1],
                                          at_top[:, 1:used + 1])
            assert np.abs(at_rung[:, 1:used]).min(axis=(0, 1, 2)).max() > 0
            for pool in (at_rung, at_top):
                # past the prompt: the rest of its last page and every
                # other page but scratch page 0 are as they were
                assert not pool[:, used, n % PAGE:].any()
                assert not pool[:, used + 1:].any()
        # an expert model's load counts the prompt's positions alone
        for at_rung, at_top in zip(got[rung][3:], got[PROMPT][3:]):
            np.testing.assert_array_equal(at_rung, at_top)
            assert at_rung.sum() == at_rung.shape[0] * n * 3
    finally:
        engine.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_greedy_tokens_either_side_of_an_edge_are_the_one_rung_engines(
        family, monkeypatch):
    prompts = [prompt_of(n) for n in (RUNGS[0], RUNGS[0] + 1, 5)]
    laddered = build(family)
    try:
        got = serve(laddered, prompts)
        shapes = laddered.stats()["prefill_shapes"]
    finally:
        laddered.close()
    assert shapes == {RUNGS[0]: 2, RUNGS[1]: 1}
    monkeypatch.setattr(engine_module, "prefill_rungs",
                        lambda max_prompt_len, page: (max_prompt_len,))
    one_rung = build(family)
    try:
        want = serve(one_rung, prompts)
        assert one_rung.stats()["prefill_shapes"] == {PROMPT: 3}
    finally:
        one_rung.close()
    assert got == want
    assert all(len(tokens) == NEW for tokens in got)


# ------------------------------------------- no request meets a compile

def test_nothing_compiles_after_the_first_admission():
    engine = build("llama-dense", max_prompt_len=512, page=16)
    rungs, lengths = (128, 256, 512), (100, 128, 129, 256, 257, 512)

    def compiled():
        return (engine.stats()["first_call_s"],
                engine._prefill_donating._cache_size(),
                engine._decode_donating._cache_size())

    async def run():   # one event loop an engine
        async def one(n):
            return [t async for t in engine.generate(prompt_of(n), 2)]
        await one(3)
        first = compiled()
        for n in lengths:
            assert len(await one(n)) == 2
        return first
    try:
        assert engine._rungs == rungs
        # a readiness check's calls first: the views' jitted programs
        table = np.zeros((BATCH, engine._maxp), np.int32)
        table[0] = np.arange(1, engine._maxp + 1)
        _, kp, vp = engine._prefill(
            engine._params, np.zeros((1, 512), np.int32), np.int32(9),
            engine._k_pages, engine._v_pages, table[:1])
        engine._decode(engine._params, np.zeros(BATCH, np.int32),
                       np.full(BATCH, 9, np.int32), kp, vp, table)
        del kp, vp
        first = asyncio.run(run())
        # one entry each: the views' two calls above.  The loop's programs
        # are the rungs' own, prefill and decode, compiled at construction
        # (a rung's program hands the pools on as a jitted one does)
        assert first[1:] == (1, 1)
        assert set(first[0]) == {f"prefill@{r}" for r in rungs} | {
            f"decode@{w}" for w in engine._decode_rungs}
        assert all(seconds > 0 for seconds in first[0].values())
        assert compiled() == first
        stats = engine.stats()
        assert stats["prefill_shapes"] == {128: 3, 256: 2, 512: 2}
        assert sum(stats["prefill_shapes"].values()) == stats["admitted"]
        assert stats["prefill_tokens"] == 3 + sum(lengths)
        assert stats["prefill_padded_tokens"] == sum(
            rung * calls for rung, calls in stats["prefill_shapes"].items())
        assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    finally:
        engine.close()


def test_one_rung_is_the_one_program_and_the_counters_say_so():
    engine = build("gpt", max_prompt_len=64)
    try:
        assert engine._rungs == (64,)
        serve(engine, [prompt_of(5), prompt_of(64)], new=2)
        stats = engine.stats()
        assert stats["prefill_shapes"] == {64: 2}
        assert stats["prefill_padded_tokens"] == 128
        # 64 + 8 positions are nine pages: decode rungs three pages apart
        assert set(stats["first_call_s"]) == {"prefill@64"} | {
            f"decode@{w}" for w in (3, 6, 9)}
        assert engine._decode_donating._cache_size() == 0
    finally:
        engine.close()


def test_a_rung_that_fails_to_compile_fails_the_prompts_that_need_it(
        monkeypatch):
    real = InferenceEngine._compile_rung

    def compile_rung(self, rung, *shapes):
        if rung == RUNGS[0]:
            raise RuntimeError("the compiler refused this rung")
        return real(self, rung, *shapes)
    monkeypatch.setattr(InferenceEngine, "_compile_rung", compile_rung)
    engine = build("llama-dense")
    try:
        async def run():
            long = [t async for t in engine.generate(prompt_of(200), 2)]
            with pytest.raises(RuntimeError, match="refused this rung"):
                [t async for t in engine.generate(prompt_of(5), 2)]
            again = [t async for t in engine.generate(prompt_of(200), 2)]
            return long, again
        long, again = asyncio.run(run())
        assert long == again and len(long) == 2
        assert engine.stats()["retired"]["error"] == 1
    finally:
        engine.close()
