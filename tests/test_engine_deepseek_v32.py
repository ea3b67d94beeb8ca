"""The serving engine with DeepSeek-V3.2-Exp's block (ISSUE 57): the second
pool a position (the indexer's keys) made, donated and counted beside the
latent pages; a prompt run as chunks against the sequence's own pages through
the scheduler, to the token the one-call engine gives; the counters and the
regions' attributes; chunking refused where the past is not addressable by
position."""

import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.engine.engine import prefill_rungs
from test_llama_deepseek_v32 import CFG, build

ENGINE = dict(model="llama", page_size=8, num_pages=40, max_batch=2,
              max_prompt_len=48, max_new_tokens=8)
PROMPTS = [list(range(1, 38)), list(range(5, 16)), list(range(3, 51))]


async def _all(engine, prompts, n=6):
    async def one(prompt):
        return [t async for t in engine.generate(prompt, n)]
    return await asyncio.gather(*map(one, prompts))


def served(chunk, params):
    engine = InferenceEngine(EngineConfig(
        model_config=CFG, prefill_chunk=chunk, **ENGINE), params=params)
    try:
        return asyncio.run(_all(engine, PROMPTS)), engine.stats(), engine
    finally:
        engine.close()


@pytest.fixture(scope="module")
def runs():
    params = build(CFG)
    return {chunk: served(chunk, params) for chunk in (0, 16)}


def test_chunks_through_the_scheduler_give_the_one_calls_tokens(runs):
    whole, chunked = runs[0][0], runs[16][0]
    assert whole == chunked and all(len(t) == 6 for t in whole)
    assert len({tuple(t) for t in whole}) == 3


def test_the_two_pools_and_the_counters(runs):
    for chunk, (_, stats, engine) in runs.items():
        # [2, 40, 8, 128] latent pages and [2, 40, 8, 16] keys, float32
        assert stats["kv_page_kind"] == "latent"
        assert stats["index_pool_bytes"] == 2 * 40 * 8 * 16 * 4
        assert stats["kv_pool_bytes"] == 2 * 40 * 8 * (128 + 16) * 4
        assert stats["kv_bytes_per_token"] == 2 * (128 + 16) * 4
        assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
        assert stats["decode"]["paged_read"]["gather"] == stats["steps"]
        # a sequence's attention reads min(8, pos + 1) of what it holds
        assert stats["dsa_live_positions"] == stats["kv_live_token_steps"]
        assert 0 < stats["dsa_selected_positions"] \
            <= 8 * stats["slot_steps"] < stats["dsa_live_positions"]
        assert stats["prefill_tokens"] == sum(map(len, PROMPTS))
        assert engine._rungs == prefill_rungs(chunk or 48, 8)
    # an indexer's prefill is a chunk's whether or not the engine cuts it
    assert [r[1]["prefill"]["attention"] for r in runs.values()] == [
        {"dense": 0, "flash": 0, "latent_chunk": calls} for calls in (3, 7)]
    assert runs[0][1]["prefill_chunks"] == 0
    assert runs[0][1]["prefill_shapes"] == {48: 3}
    # 37 = 16 + 16 + 5, 11, 48 = 3 x 16: seven calls of the one rung
    assert runs[16][1]["prefill_chunks"] == 7
    assert runs[16][1]["prefill_chunk_tokens"] == sum(map(len, PROMPTS))
    assert runs[16][1]["prefill_shapes"] == {16: 7}
    assert runs[16][1]["prefill_padded_tokens"] == 7 * 16
    assert runs[16][1]["admitted"] == 3


def test_the_regions_say_where_a_chunk_lies(runs, monkeypatch):
    """``rt:engine.prefill`` of a chunked engine is the CHUNK's (its real
    positions as ``prompt_len``, its ``start``, ``width`` and ``rung``) and
    every chunk of a prompt carries the wait its first one found, and what
    the model's selector says its attention runs as (the chunk's kernel) is
    the region's ``attention`` and the key
    ``stats()["prefill"]["attention"]`` counts it under;
    ``rt:engine.decode.dispatch`` carries ``selected`` and ``live``."""
    from ray_tpu.serve.engine import engine as module
    seen = []

    class Region:
        def __init__(self, name, **attrs):
            seen.append((name, attrs))

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False
    monkeypatch.setattr(module, "region", Region)
    engine = InferenceEngine(EngineConfig(
        model_config=CFG, prefill_chunk=16, **ENGINE),
        params=runs[16][2]._params)
    try:
        asyncio.run(_all(engine, PROMPTS[:2], 3))
        counted = engine.stats()["prefill"]["attention"]
    finally:
        engine.close()
    chunks = [a for name, a in seen if name == "engine.prefill"]
    assert {c["attention"] for c in chunks} == {"latent_chunk"}
    assert counted == {"dense": 0, "flash": 0, "latent_chunk": 4}
    assert [(c["start"], c["width"], c["rung"], c["prompt_len"],
             c["padded_len"]) for c in chunks] == [
        (0, 16, 16, 16, 16), (16, 16, 16, 16, 16), (32, 5, 16, 5, 16),
        (0, 11, 16, 11, 16)]
    assert len({c["waited_us"] for c in chunks[:3]}) == 1
    # one prompt a pass: the first sequence steps before the second's chunk
    order = [name for name, _ in seen if name in (
        "engine.prefill", "engine.decode.dispatch")]
    assert order[:5] == ["engine.prefill"] * 3 + [
        "engine.decode.dispatch", "engine.prefill"]
    steps = [a for name, a in seen if name == "engine.decode.dispatch"]
    assert steps and all(s["selected"] == 8 * s["active"]
                         and s["live"] == s["live_tokens"] for s in steps)
    assert steps[0]["active"] == 1 and steps[0]["live"] == 38
    moe = [a for name, a in seen if name == "engine.prefill.moe"]
    assert [m["assignments_made"] for m in moe] == [16 * 4, 16 * 4, 5 * 4,
                                                     11 * 4]


@pytest.mark.parametrize("kind", ["linear", "conv", "kv"])
def test_chunking_is_refused_where_the_past_is_no_pages(kind):
    from ray_tpu.models.llama import LlamaConfig
    if kind == "kv":
        model = LlamaConfig.tiny(seq=32)
    elif kind == "conv":
        from test_llama_lfm2 import PLAIN as model
    else:
        from test_llama_hybrid import CFG as model
    with pytest.raises(ValueError, match="addressable by position"):
        InferenceEngine(EngineConfig(
            model="llama", model_config=model, page_size=8, num_pages=16,
            max_batch=2, max_prompt_len=16, max_new_tokens=8,
            prefill_chunk=8))


def test_a_chunk_is_whole_pages():
    with pytest.raises(ValueError, match="whole pages"):
        InferenceEngine(EngineConfig(model_config=CFG, prefill_chunk=12,
                                     **ENGINE))
