"""The engine with LFM2's stack (ISSUE 55): a convolution tail a decode slot
beside K/V pages and NO state matrix, experts behind a dense layer with no
shared expert, a tied head.  Six requests through four slots give each
sequence the tokens it gets alone, with retirement and admission in between,
so a slot is reused and a stale tail would show; a prefill that wrote the
wrong slot does show; ``stats()`` has the tails' bytes, no state matrix's,
and the K/V pool's."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from test_llama_lfm2 import CFG as LLAMA_CFG, build

PAGE, PROMPT, NEW, BATCH = 4, 32, 16, 4
SEQ = PROMPT + NEW
CFG = dataclasses.replace(LLAMA_CFG, max_seq_len=SEQ)
PROMPTS = (5, 17, 1, 30, 2, 21)
NEWS = [16, 7, 12, 16, 5, 9]


@pytest.fixture(scope="module")
def params():
    return build(CFG)


def engine_of(params):
    return InferenceEngine(EngineConfig(
        model="llama", model_config=CFG, page_size=PAGE,
        num_pages=BATCH * (SEQ // PAGE) + 1, max_batch=BATCH,
        max_prompt_len=PROMPT, max_new_tokens=NEW), params=params)


@pytest.fixture(scope="module")
def wanted(params):
    """Greedy generation by the full forward, nothing cached."""
    forward = jax.jit(lambda t: llama.llama_forward(params, t, CFG))
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, n).tolist() for n in PROMPTS]
    out = []
    for prompt, new in zip(prompts, NEWS):
        seq = list(prompt)
        for _ in range(new):
            padded = np.zeros((1, SEQ), np.int32)
            padded[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(forward(padded)[0, len(seq) - 1])))
        out.append(seq[len(prompt):])
    return prompts, out


def serve(engine, prompts):
    async def main():
        async def one(prompt, new):
            return [t async for t in engine.generate(prompt, new)]
        return await asyncio.gather(*map(one, prompts, NEWS))
    return asyncio.run(main())


def test_six_requests_through_four_slots_get_what_they_get_alone(
        params, wanted):
    prompts, alone = wanted
    assert len({tuple(tokens) for tokens in alone}) == 6
    assert all(len(set(tokens)) > 2 for tokens in alone)
    engine = engine_of(params)
    try:
        assert serve(engine, prompts) == alone
        stats = engine.stats()
    finally:
        engine.close()
    assert stats["retired"]["done"] == 6 and stats["admitted"] == 6
    assert stats["decode_ahead_steps"] > stats["steps"] // 2   # ran ahead
    assert stats["state_rows_written"] == 6     # two slots were used twice
    # 4 conv layers x 4 slots x 2 positions x 64 f32 values: tails, and
    # nothing else a slot
    assert stats["conv_tail_bytes"] == 4 * 4 * 2 * 64 * 4
    assert stats["recurrent_state_bytes"] == stats["conv_tail_bytes"]
    assert stats["recurrent_matrix_bytes"] == 0
    # the pages are the 2 attention layers' alone: 2 K/V heads of 16
    assert stats["kv_pool_layers"] == 2 and stats["kv_page_kind"] == "kv"
    assert stats["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert stats["kv_pool_bytes"] == 2 * 2 * (BATCH * (SEQ // PAGE) + 1) \
        * PAGE * 32 * 4
    assert 0.0 < stats["recurrent_step_bytes_share"] < 0.2
    assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    # no linear layer: nothing steps a state, by kernel or by rule
    assert stats["decode"]["linear_state"] == {"kernel": 0, "rule": 0}
    assert stats["decode"]["paged_read"]["gather"] == stats["steps"]
    # every real token makes 4 assignments in each of the 5 expert layers,
    # and the program holds every expert
    tokens = sum(PROMPTS) + stats["slot_steps"]
    assert stats["moe_assignments_made"] == tokens * 5 * 4 == \
        stats["moe_assignments"]
    assert np.asarray(stats["moe_load"]).shape == (5, 8)
    # the tied head: the table once
    assert stats["weight_bytes"] == 4 * sum(
        a.size for a in jax.tree.leaves(params))


def test_a_prefill_that_writes_the_wrong_slots_tail_is_seen(
        params, wanted, monkeypatch):
    """The stale-tail fault planted: every prefill leaves its tail in slot
    0, so a sequence stepped in another slot goes on from what that slot's
    last sequence left (or from nothing)."""
    real = llama.llama_prefill
    monkeypatch.setattr(
        llama, "llama_prefill",
        lambda params, cfg, tokens, length, kp, vp, table, slot=0: real(
            params, cfg, tokens, length, kp, vp, table, 0))
    faulty = engine_of(params)
    try:
        prompts, alone = wanted
        got = serve(faulty, prompts)
    finally:
        faulty.close()
    assert got != alone
    assert [a[0] for a in got] == [a[0] for a in alone]   # the prefill's own


def test_a_model_with_a_state_still_says_what_its_rows_are():
    """The hybrid's and Kimi's numbers stand (their tests pin
    ``recurrent_state_bytes`` as states + tails): the tails are named beside
    them, the state matrices are the rest."""
    from test_llama_hybrid import CFG as HYBRID
    eng = InferenceEngine(EngineConfig(
        model="llama", model_config=dataclasses.replace(HYBRID,
                                                        max_seq_len=24),
        page_size=4, num_pages=13, max_batch=2, max_prompt_len=16,
        max_new_tokens=8))
    try:
        stats = eng.stats()
    finally:
        eng.close()
    assert 0 < stats["conv_tail_bytes"] < stats["recurrent_state_bytes"]
    assert stats["recurrent_matrix_bytes"] == \
        stats["recurrent_state_bytes"] - stats["conv_tail_bytes"]
