"""The engine with latent pages (``models/llama.py`` with ``kv_lora_rank``):
ONE pool ``[L, P, page, Wp]`` (``Wp`` = rank + rope rounded up to whole
128-lane tiles, the columns past rank + rope zero) and no V pool, through
the same loop, ladder, donation, views and counters as the K/V-page models,
at tiny widths on the CPU.

Admission, retirement and page reuse with more callers than slots and a full
batch give each caller the tokens a single-sequence run gives; the pool is
donated and comes back in place; a failed call leaves a fresh pool; the
consuming views hold one pool; ``stats()`` says the kind and its bytes, and
counts the experts' load for the layers that route.
"""

import asyncio
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from test_engine_prefill_rungs import failing

PAGE, PROMPT, NEW, BATCH = 8, 32, 16, 4
MAXP = (PROMPT + NEW) // PAGE
W, WP = 32 + 8, 128         # a row's values, and as the pool pads them
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=PROMPT + NEW, num_layers=3, num_heads=4,
    num_kv_heads=4, embed_dim=64, mlp_dim=32, num_experts=8,
    experts_per_token=2, norm_topk_prob=True, kv_lora_rank=32,
    q_lora_rank=48, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=12,
    rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0), first_dense_layers=1,
    dense_mlp_dim=96, shared_experts=1, router_scoring="sigmoid",
    router_bias=True, routed_scaling=2.0, hc_mult=4, dtype=jnp.float32)


def build(num_pages=BATCH * MAXP + 1, max_batch=BATCH, **model):
    cfg = LlamaConfig(**{**CFG.__dict__, **model})
    params = llama.llama_init(jax.random.PRNGKey(3), cfg)
    return params, InferenceEngine(EngineConfig(
        model="llama", model_config=cfg, page_size=PAGE,
        num_pages=num_pages, max_batch=max_batch, max_prompt_len=PROMPT,
        max_new_tokens=NEW), params=params)


def prompts(n, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 97, int(rng.integers(3, PROMPT + 1))).tolist()
            for _ in range(n)]


def test_the_pool_is_one_array_of_latent_pages():
    _, engine = build()
    try:
        assert engine._v_pages is None
        assert engine._k_pages.shape == (3, BATCH * MAXP + 1, PAGE, WP)
        stats = engine.stats()
        assert stats["kv_page_kind"] == "latent"
        assert stats["kv_pool_layers"] == 3
        assert stats["kv_bytes_per_token"] == 3 * WP * 4         # float32
        assert stats["kv_pool_bytes"] == engine._k_pages.nbytes
    finally:
        engine.close()
    _, plain = build(kv_lora_rank=0, hc_mult=0)
    try:
        assert plain.stats()["kv_page_kind"] == "kv"
        assert plain._v_pages.shape == plain._k_pages.shape
    finally:
        plain.close()


def test_a_full_batch_with_waiting_callers_agrees_with_single_runs():
    """Ten callers for four slots and pages for four sequences: admission
    waits for a retirement, retired pages are handed out again, the batch is
    full most of the time, and every caller gets the tokens it gets alone."""
    asked = prompts(10)
    news = [4 + 3 * (i % 5) for i in range(10)]
    _, engine = build()

    async def one(engine, prompt, new):
        return [t async for t in engine.generate(prompt, new)]

    async def together():
        return await asyncio.gather(*(one(engine, p, n)
                                      for p, n in zip(asked, news)))
    try:
        got = asyncio.run(together())
        stats = engine.stats()
    finally:
        engine.close()
    assert stats["admitted"] == 10 and stats["retired"]["done"] == 10
    assert stats["free_pages"] == BATCH * MAXP       # every page came back
    assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    assert stats["slot_steps"] > 2 * stats["steps"]  # more than two live
    assert stats["moe_assignments"] > 0
    # two of the three layers route: hits are counted for those alone
    assert stats["moe_experts_hit"] <= 2 * 8 * (stats["steps"] + 10)
    # a step gathers its rung's pages for every slot (``decode_rungs``)
    assert sum(stats["decode_shapes"].values()) == stats["steps"]
    assert stats["kv_gathered_token_steps"] == sum(
        steps * BATCH * width * PAGE
        for width, steps in stats["decode_shapes"].items())
    assert 0 < stats["kv_live_token_steps"] < stats["kv_gathered_token_steps"]

    _, alone = build(num_pages=MAXP + 1, max_batch=1)

    async def in_turn():
        return [await one(alone, p, n) for p, n in zip(asked, news)]
    try:
        want = asyncio.run(in_turn())
    finally:
        alone.close()
    assert [len(g) for g in got] == news
    assert got == want


def test_prefill_then_decode_is_the_full_forward():
    """Logits, not tokens: the engine's two programs through the latent
    pages against ``llama_forward`` on the whole sequence."""
    params, engine = build()
    try:
        tokens = np.asarray(prompts(1, seed=5)[0][:20] + [7] * 6, np.int32)
        length = 20
        want = np.asarray(llama.llama_forward(params, tokens[None],
                                              engine.model_config)[0])
        table = np.zeros((BATCH, MAXP), np.int32)
        table[2] = np.arange(1, MAXP + 1)              # the third slot
        padded = np.zeros((1, PROMPT), np.int32)
        padded[0, :length] = tokens[:length]
        logits, kp, vp = engine._prefill(
            engine._params, padded, np.int32(length), engine._k_pages,
            engine._v_pages, table[2:3])
        assert vp is None and kp is engine._k_pages
        got = [np.asarray(logits[0])]
        tok, pos = np.zeros((BATCH,), np.int32), np.zeros((BATCH,), np.int32)
        for at in range(length, len(tokens)):
            tok[2], pos[2] = tokens[at], at
            logits, kp, vp = engine._decode(engine._params, tok, pos, kp, vp,
                                            table)
            got.append(np.asarray(logits[2]))
        np.testing.assert_allclose(np.stack(got), want[length - 1:],
                                   rtol=2e-4, atol=2e-5)
    finally:
        engine.close()


def test_a_shorter_rung_gives_the_logits_and_pages_of_the_top_rung():
    _, engine = build()
    try:
        assert engine._rungs == (PROMPT,)
    finally:
        engine.close()
    cfg = LlamaConfig(**{**CFG.__dict__, "max_seq_len": 256 + NEW})
    params = llama.llama_init(jax.random.PRNGKey(3), cfg)
    engine = InferenceEngine(EngineConfig(
        model="llama", model_config=cfg, page_size=PAGE, num_pages=70,
        max_batch=2, max_prompt_len=256, max_new_tokens=NEW), params=params)
    try:
        assert engine._rungs == (128, 256)
        prompt = prompts(1, seed=9)[0][:21]
        table = np.zeros((1, engine._maxp), np.int32)
        table[0] = np.arange(1, engine._maxp + 1)
        out = []
        for rung in engine._rungs:
            padded = np.zeros((1, rung), np.int32)
            padded[0, :len(prompt)] = prompt
            logits, kp, _, load = engine._prefill_program(
                engine._params, padded, np.int32(len(prompt)),
                engine._k_pages, engine._v_pages, table)
            # the pages the prompt holds (21 positions: three pages, the
            # third's last three slots are padding the decode overwrites)
            held = np.asarray(kp[:, 1:4]).reshape(3, 3 * PAGE, WP)[:, :21]
            # written rows, their padding columns left zero
            assert held[:, 0, :W].all() and not held[..., W:].any()
            out.append((np.asarray(logits), held, np.asarray(load)))
            assert not np.asarray(kp[:, 4:]).any()     # nothing past them
        for a, b in zip(*out):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-6)
    finally:
        engine.close()


def test_a_failed_decode_leaves_a_fresh_pool(monkeypatch):
    _, engine = build()

    async def ask():
        return [t async for t in engine.generate([3, 1, 4, 1, 5], 4)]

    async def scenario():
        want = await ask()
        for width, compiled in list(engine._decode_programs.items()):
            monkeypatch.setitem(engine._decode_programs, width,
                                failing(compiled.result()))
        with pytest.raises(RuntimeError, match="fell over"):
            await ask()
        monkeypatch.undo()
        assert engine.stats()["retired"]["error"] == 1
        assert not engine._k_pages.is_deleted() and engine._v_pages is None
        assert await ask() == want
    try:
        asyncio.run(scenario())
    finally:
        engine.close()


def test_the_views_copy_or_consume_as_for_two_pools():
    _, engine = build()
    try:
        table = np.zeros((1, MAXP), np.int32)
        table[0] = np.arange(1, MAXP + 1)
        padded = np.zeros((1, PROMPT), np.int32)
        padded[0, :5] = [3, 1, 4, 1, 5]
        args = (engine._params, padded, np.int32(5), engine._k_pages,
                engine._v_pages, table)
        first = engine._prefill_program(*args)         # on a copy
        assert not engine._k_pages.is_deleted()
        assert not np.asarray(engine._k_pages).any()
        assert len(first) == 4 and first[2] is None
        before = engine._k_pages
        logits, kp, vp = engine._prefill(*args)        # consumes, keeps
        assert before.is_deleted() and kp is engine._k_pages and vp is None
        np.testing.assert_array_equal(np.asarray(logits),
                                      np.asarray(first[0]))
        gc.collect()
        alive = sum(a.shape == kp.shape and a.dtype == kp.dtype
                    and not a.is_deleted() for a in jax.live_arrays())
        assert alive == 2          # the engine's pool and ``first``'s copy
    finally:
        engine.close()
