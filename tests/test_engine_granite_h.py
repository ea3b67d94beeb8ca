"""The engine with Granite 4.0-H's stack (ISSUE 61): a float32 state row and
a convolution tail a decode slot for the Mamba-2 layers beside the one
attention layer's K/V pages, experts in every layer of which the program
holds a share.  Five requests through two slots give each sequence the tokens
it gets ALONE (the same engine, one request at a time: what the programs
give against ``llama_forward`` and the reference is ``test_llama_ssm.py``'s
and ``tests/benchmark/test_granite_hybrid_reference.py``'s), with retirement
and admission in between, so a slot is used again and its row has to start
from the new prompt alone; the state pool is
held once (the consuming views hand the engine's own buffers back);
``stats()`` has the pool's bytes and the step's kind."""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from test_llama_ssm import CFG as LLAMA_CFG

PAGE, PROMPT, NEW, BATCH = 4, 16, 8, 2
SEQ = PROMPT + NEW
CFG = dataclasses.replace(LLAMA_CFG, max_seq_len=SEQ)
PROMPTS = (5, 13, 1, 16, 9)
NEWS = [8, 3, 6, 8, 4]


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


def test_five_requests_through_two_slots_get_what_they_get_alone(engine):
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, 97, n).tolist() for n in PROMPTS]

    async def one(prompt, new):
        return [t async for t in engine.generate(prompt, new)]

    async def main():
        alone = [await one(*request) for request in zip(prompts, NEWS)]
        return alone, await asyncio.gather(*map(one, prompts, NEWS))
    alone, together = asyncio.run(main())
    assert together == alone
    assert len({tuple(tokens) for tokens in alone}) == 5
    stats = engine.stats()
    assert stats["retired"]["done"] == 10 and stats["admitted"] == 10
    assert stats["state_rows_written"] == 10    # every slot used again
    # 2 ssm layers x 2 slots: a [8, 16, 16] float32 state, a tail of 3 x 160
    assert stats["recurrent_matrix_bytes"] == 2 * 2 * 8 * 16 * 16 * 4
    assert stats["conv_tail_bytes"] == 2 * 2 * 3 * 160 * 4
    assert stats["recurrent_state_bytes"] == \
        stats["recurrent_matrix_bytes"] + stats["conv_tail_bytes"]
    # the pages are the ONE attention layer's: 2 K/V heads of 16
    assert stats["kv_pool_layers"] == 1 and stats["kv_page_kind"] == "kv"
    assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    # the CPU steps the states by the rule; the chip by the kernel
    assert stats["decode"]["linear_state"] == {"kernel": 0,
                                               "rule": stats["steps"]}
    # every real token makes 3 assignments in each of the 3 layers, of
    # which the half the program holds are kept
    tokens = 2 * sum(PROMPTS) + stats["slot_steps"]
    assert stats["moe_assignments_made"] == tokens * 3 * 3
    assert 0 < stats["moe_assignments"] < stats["moe_assignments_made"]
    assert np.asarray(stats["moe_load"]).shape == (3, 4)


def test_a_check_by_the_consuming_views_holds_one_state_pool(engine):
    """``benchmark/replica_states.py``'s ``check_numerics``: ``_prefill`` on
    the engine's pools, ``_decode`` on what it returned: every call hands
    back the engine's own state pool, the buffer that went in is gone, and
    no second array of the pool's shape is alive."""
    shape = engine._v_pages.state.shape

    def alive():
        return sum(a.shape == shape and a.dtype == jnp.float32
                   and not a.is_deleted() for a in jax.live_arrays())
    assert alive() == 1
    table = np.zeros((BATCH, engine._maxp), np.int32)
    table[0] = np.arange(1, engine._maxp + 1)
    padded = np.zeros((1, PROMPT), np.int32)
    before = engine._v_pages.state
    logits, kp, vp = engine._prefill(
        engine._params, padded, np.int32(7), engine._k_pages,
        engine._v_pages, table[:1])
    assert before.is_deleted() and vp is engine._v_pages and alive() == 1
    tok, pos = np.zeros((BATCH,), np.int32), np.zeros((BATCH,), np.int32)
    pos[0] = 7
    before = vp.state
    rows = np.stack([np.array(vp.state[:, slot]) for slot in range(BATCH)],
                    axis=1)
    logits, kp, vp = engine._decode(engine._params, tok, pos, kp, vp, table)
    assert before.is_deleted() and vp is engine._v_pages and alive() == 1
    assert np.abs(np.asarray(vp.state[:, 0]) - rows[:, 0]).max() > 0
    np.testing.assert_array_equal(vp.state[:, 1], rows[:, 1])    # parked
