"""The engine with a model that keeps a row a decode slot beside pages
(ISSUE 48): six requests through four slots give each sequence the tokens it
gets alone, with retirement and admission in between and the loop a step
ahead; the prefill learns its slot; ``stats()`` has the rows' bytes apart."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama, serving_model
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

PAGE, PROMPT, NEW, BATCH = 4, 32, 16, 4
SEQ = PROMPT + NEW
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=SEQ, num_layers=8, num_heads=4,
    num_kv_heads=4, embed_dim=32, mlp_dim=48, dtype=jnp.float32,
    attention="dense", remat=False, rope_theta=0.0, rms_eps=1e-6,
    qk_norm=True, pre_norm=False, post_norm=True,
    layer_pattern=("linear", "linear", "linear", "full"), linear_heads=2,
    linear_key_dim=8, linear_value_dim=192, linear_neg_eigval=True)


@pytest.fixture(scope="module")
def params():
    return llama.llama_init(jax.random.PRNGKey(1), CFG)


@pytest.fixture(scope="module")
def engine(params):
    eng = InferenceEngine(EngineConfig(
        model="llama", model_config=CFG, page_size=PAGE,
        num_pages=BATCH * (SEQ // PAGE) + 1, max_batch=BATCH,
        max_prompt_len=PROMPT, max_new_tokens=NEW), params=params)
    yield eng
    eng.close()


def alone(params, prompt, new):
    """Greedy generation by the full forward, nothing cached."""
    forward = jax.jit(lambda t: llama.llama_forward(params, t, CFG))
    seq = list(prompt)
    for _ in range(new):
        padded = np.zeros((1, SEQ), np.int32)
        padded[0, :len(seq)] = seq
        seq.append(int(jnp.argmax(forward(padded)[0, len(seq) - 1])))
    return seq[len(prompt):]


def test_six_requests_through_four_slots_get_what_they_get_alone(
        engine, params):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, n).tolist() for n in (5, 17, 9, 30, 12,
                                                         21)]
    news = [16, 7, 12, 16, 5, 9]

    async def main():
        async def one(prompt, new):
            return [t async for t in engine.generate(prompt, new)]
        return await asyncio.gather(*map(one, prompts, news))

    got = asyncio.run(main())
    for prompt, new, tokens in zip(prompts, news, got):
        assert tokens == alone(params, prompt, new)
    stats = engine.stats()
    assert stats["retired"]["done"] == 6 and stats["admitted"] == 6
    assert stats["decode_ahead_steps"] > stats["steps"] // 2   # ran ahead
    assert stats["state_rows_written"] == 6
    # 6 linear layers x 4 slots x (2 x 8 x 192 f32 + 3 x 416 f32 tails)
    assert stats["recurrent_state_bytes"] == 6 * 4 * (
        2 * 8 * 192 * 4 + 3 * 416 * 4)
    # the pages are the 2 full layers' alone
    assert stats["kv_pool_layers"] == 2 and stats["kv_page_kind"] == "kv"
    assert stats["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert 0.05 < stats["recurrent_step_bytes_share"] < 0.95
    assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    # on this backend every step's program steps the states by the rule
    assert stats["decode"]["linear_state"] == {"kernel": 0,
                                               "rule": stats["steps"]}


def test_the_engine_on_the_kernel_gives_the_same_tokens(params, monkeypatch):
    """The decode programs traced as on the chip (ISSUE 52): the linear
    layers' states stepped by ``ops/linear_state.py`` where the pool holds
    them (here in the interpreter), slots parked beside live ones, a slot
    reused; the tokens are those each sequence gets alone, and ``stats()``
    and the dispatch regions count the steps under "kernel"."""
    from ray_tpu.ops import linear_attention
    monkeypatch.setattr(linear_attention, "_kernel_backend", lambda: True)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 97, n).tolist() for n in (7, 19, 11, 26, 5)]
    news = [9, 4, 7, 6, 8]
    eng = InferenceEngine(EngineConfig(
        model="llama", model_config=CFG, page_size=PAGE,
        num_pages=BATCH * (SEQ // PAGE) + 1, max_batch=BATCH,
        max_prompt_len=PROMPT, max_new_tokens=NEW), params=params)
    try:
        async def main():
            async def one(prompt, new):
                return [t async for t in eng.generate(prompt, new)]
            return await asyncio.gather(*map(one, prompts, news))
        got = asyncio.run(main())
        stats = eng.stats()
    finally:
        eng.close()
    for prompt, new, tokens in zip(prompts, news, got):
        assert tokens == alone(params, prompt, new)
    assert stats["state_rows_written"] == 5
    assert stats["steps"] > 0
    assert stats["decode"]["linear_state"] == {"kernel": stats["steps"],
                                               "rule": 0}


def test_the_views_take_the_slot_last_and_default_to_the_first(engine):
    """What ``benchmark/replica.py`` calls: ``_prefill`` with six arguments
    writes slot 0's rows; a seventh names another slot."""
    eng, cfg = engine, engine.config
    tokens = np.zeros((1, cfg.max_prompt_len), np.int32)
    tokens[0, :9] = np.arange(1, 10)
    table = np.zeros((cfg.max_batch, eng._maxp), np.int32)
    table[0] = np.arange(1, eng._maxp + 1)
    fresh = eng._new_pools()
    _, _, vp = eng._prefill_program(eng._params, tokens, np.int32(9),
                                    *fresh, table[:1])
    assert np.asarray(vp.state[:, 0]).any()
    assert not np.asarray(vp.state[:, 1:]).any()
    _, _, vp = eng._prefill_program(eng._params, tokens, np.int32(9),
                                    *fresh, table[:1], np.int32(2))
    assert np.asarray(vp.state[:, 2]).any()
    assert not np.asarray(vp.state[:, [0, 1, 3]]).any()
    # the consuming view: the engine's own pools go in and come back
    logits, kp, vp = eng._prefill(eng._params, tokens, np.int32(9),
                                  eng._k_pages, eng._v_pages, table[:1])
    assert vp is eng._v_pages and logits.shape == (1, 97)
    tok = np.zeros((cfg.max_batch,), np.int32)
    pos = np.zeros((cfg.max_batch,), np.int32)
    tok[0], pos[0] = 5, 9
    logits, kp, vp = eng._decode(eng._params, tok, pos, kp, vp, table)
    assert vp is eng._v_pages and logits.shape == (cfg.max_batch, 97)


def test_the_record_says_which_arrays_are_rows_a_slot():
    served = serving_model("llama", CFG)
    kp, vp = served.new_pools(9, PAGE, None, 3)
    rows = served.slot_rows(kp, vp)
    assert [a.shape[:2] for a in rows] == [(6, 3), (6, 3)]
    plain = serving_model("llama", LlamaConfig.tiny(seq=SEQ))
    assert plain.slot_rows is None
    assert len(plain.new_pools(9, PAGE, None, 3)) == 2      # slots ignored
    assert serving_model("gpt", None, SEQ).slot_rows is None
    assert len(serving_model("gpt", None, SEQ).new_pools(9, PAGE, None, 3)) \
        == 2


def test_a_model_without_rows_reports_none():
    eng = InferenceEngine(EngineConfig(
        model="llama", page_size=PAGE, num_pages=9, max_batch=2,
        max_prompt_len=8, max_new_tokens=8))
    try:
        stats = eng.stats()
        assert "recurrent_state_bytes" not in stats
        assert "state_rows_written" not in stats
        assert stats["decode"]["linear_state"] == {"kernel": 0, "rule": 0}
    finally:
        eng.close()
