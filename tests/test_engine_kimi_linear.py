"""The engine with Kimi Linear's stack (ISSUE 51): a state row and a
convolution tail a decode slot beside LATENT pages, experts of which the
program holds a share.  Six requests through four slots give each sequence
the tokens it gets alone, with retirement and admission in between, so a slot
is reused and a stale row would show; a prefill that wrote the wrong slot
does show; ``stats()`` has the held experts' load, the assignments made and
kept, and the rows' bytes apart from the latent pool's."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from test_llama_kimi_linear import CFG as LLAMA_CFG

import dataclasses

PAGE, PROMPT, NEW, BATCH = 4, 32, 16, 4
SEQ = PROMPT + NEW
CFG = dataclasses.replace(LLAMA_CFG, max_seq_len=SEQ)
PROMPTS = (5, 17, 9, 30, 12, 21)
NEWS = [16, 7, 12, 16, 5, 9]


@pytest.fixture(scope="module")
def params():
    """Output projections eight times the initialisation's: at its scale a
    tiny model answers one token whatever its cache holds."""
    tree = llama.llama_init(jax.random.PRNGKey(1), CFG)
    return jax.tree_util.tree_map_with_path(
        lambda path, a: 8.0 * a if getattr(path[-1], "key", "") in (
            "wo", "wd") else a, tree)


def engine_of(params):
    return InferenceEngine(EngineConfig(
        model="llama", model_config=CFG, page_size=PAGE,
        num_pages=BATCH * (SEQ // PAGE) + 1, max_batch=BATCH,
        max_prompt_len=PROMPT, max_new_tokens=NEW), params=params)


@pytest.fixture(scope="module")
def engine(params):
    eng = engine_of(params)
    yield eng
    eng.close()


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
        engine, wanted):
    prompts, alone = wanted
    assert len({tuple(tokens) for tokens in alone}) == 6
    assert all(len(set(tokens)) > 2 for tokens in alone)
    assert serve(engine, prompts) == alone
    stats = engine.stats()
    assert stats["retired"]["done"] == 6 and stats["admitted"] == 6
    assert stats["decode_ahead_steps"] > stats["steps"] // 2   # ran ahead
    assert stats["state_rows_written"] == 6     # two slots were used twice
    # 6 KDA layers x 4 slots x (4 x 8 x 8 f32 states + 3 x 96 f32 tails)
    assert stats["recurrent_state_bytes"] == 6 * 4 * (
        4 * 8 * 8 * 4 + 3 * 96 * 4)
    # the pages are the 2 latent layers' alone: rows of 24 + 8 in 128 lanes
    assert stats["kv_pool_layers"] == 2
    assert stats["kv_page_kind"] == "latent"
    assert stats["kv_bytes_per_token"] == 2 * 128 * 4
    assert stats["kv_pool_bytes"] == 2 * (BATCH * (SEQ // PAGE) + 1) \
        * PAGE * 128 * 4
    assert 0.0 < stats["recurrent_step_bytes_share"] < 0.95
    assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    # on this backend every step's program steps the states by the rule
    assert stats["decode"]["linear_state"] == {"kernel": 0,
                                               "rule": stats["steps"]}
    # every real token makes 4 assignments in each of the 7 expert layers;
    # this program holds share 1 of 4 and keeps what falls there
    tokens = sum(PROMPTS) + stats["slot_steps"]
    assert stats["moe_assignments_made"] == tokens * 7 * 4
    assert 0 < stats["moe_assignments"] < stats["moe_assignments_made"]
    load = np.asarray(stats["moe_load"])
    assert load.shape == (7, 4) and load.sum() == stats["moe_assignments"]
    assert 0.05 < stats["moe_assignments"] \
        / stats["moe_assignments_made"] < 0.6


@pytest.mark.parametrize("kind", ["kernel", "rule"])
def test_heads_of_whole_panels_step_by_the_kernel_to_the_same_tokens(
        params, monkeypatch, kind):
    """Heads of 128 values (whole panels, as published) and the decay a key
    channel, the decode programs traced as on the chip (ISSUE 52; "kernel":
    ``ops/linear_state.py`` in the interpreter) and as here ("rule"): three
    requests get from the engine what the full forward gives them, and
    ``stats()`` counts the steps by what stepped the states."""
    from ray_tpu.ops import linear_attention
    monkeypatch.setattr(linear_attention, "_kernel_backend",
                        lambda: kind == "kernel")
    cfg = dataclasses.replace(CFG, linear_value_dim=128)
    # the state's read-out is eight values a head no more: another tree
    tree = llama.llama_init(jax.random.PRNGKey(2), cfg)
    tree = jax.tree_util.tree_map_with_path(
        lambda path, a: 8.0 * a if getattr(path[-1], "key", "") in (
            "wo", "wd") else a, tree)
    forward = jax.jit(lambda t: llama.llama_forward(tree, t, cfg))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, 97, n).tolist() for n in (6, 15, 10)]
    news, alone = [5, 3, 4], []
    for prompt, new in zip(prompts, news):
        seq = list(prompt)
        for _ in range(new):
            padded = np.zeros((1, SEQ), np.int32)
            padded[0, :len(seq)] = seq
            seq.append(int(jnp.argmax(forward(padded)[0, len(seq) - 1])))
        alone.append(seq[len(prompt):])
    eng = InferenceEngine(EngineConfig(
        model="llama", model_config=cfg, page_size=PAGE,
        num_pages=BATCH * (SEQ // PAGE) + 1, max_batch=BATCH,
        max_prompt_len=PROMPT, max_new_tokens=NEW), params=tree)
    try:
        async def main():
            async def one(prompt, new):
                return [t async for t in eng.generate(prompt, new)]
            return await asyncio.gather(*map(one, prompts, news))
        got = asyncio.run(main())
        stats = eng.stats()
    finally:
        eng.close()
    assert got == alone
    other = "rule" if kind == "kernel" else "kernel"
    assert stats["steps"] > 0
    assert stats["decode"]["linear_state"] == {kind: stats["steps"],
                                               other: 0}


def test_a_prefill_that_writes_the_wrong_slots_rows_is_seen(
        params, wanted, monkeypatch):
    """The stale-row fault planted: every prefill leaves its state and tail
    in slot 0, so a sequence stepped in another slot goes on from what that
    slot's last sequence left (or from nothing)."""
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


def test_the_views_take_the_slot_last_and_default_to_the_first(engine):
    """What ``benchmark/replica.py`` calls: ``_prefill`` with six arguments
    writes slot 0's rows; a seventh names another slot; the pair that comes
    back is (latent pages, rows with no V pool)."""
    eng, cfg = engine, engine.config
    tokens = np.zeros((1, cfg.max_prompt_len), np.int32)
    tokens[0, :9] = np.arange(1, 10)
    table = np.zeros((cfg.max_batch, eng._maxp), np.int32)
    table[0] = np.arange(1, eng._maxp + 1)
    fresh = eng._new_pools()
    _, kp, vp = eng._prefill_program(eng._params, tokens, np.int32(9),
                                     *fresh, table[:1])[:3]
    assert vp.v_pages is None and np.asarray(kp[:, 1:4]).any()
    assert np.asarray(vp.state[:, 0]).any()
    assert not np.asarray(vp.state[:, 1:]).any()
    _, _, vp = eng._prefill_program(eng._params, tokens, np.int32(9),
                                    *fresh, table[:1], np.int32(2))[:3]
    assert np.asarray(vp.state[:, 2]).any()
    assert not np.asarray(vp.state[:, [0, 1, 3]]).any()
    logits, kp, vp = eng._prefill(eng._params, tokens, np.int32(9),
                                  eng._k_pages, eng._v_pages, table[:1])
    assert vp is eng._v_pages and logits.shape == (1, 97)
    tok = np.zeros((cfg.max_batch,), np.int32)
    pos = np.zeros((cfg.max_batch,), np.int32)
    tok[0], pos[0] = 5, 9
    logits, kp, vp = eng._decode(eng._params, tok, pos, kp, vp, table)
    assert vp is eng._v_pages and logits.shape == (cfg.max_batch, 97)


def test_an_engine_that_holds_every_expert_keeps_every_assignment():
    eng = InferenceEngine(EngineConfig(
        model="llama", page_size=PAGE, num_pages=9, max_batch=2,
        max_prompt_len=8, max_new_tokens=8,
        model_config=llama.LlamaConfig(
            vocab_size=97, max_seq_len=16, num_layers=2, num_heads=2,
            num_kv_heads=2, embed_dim=16, mlp_dim=8, num_experts=4,
            experts_per_token=2, dtype=jnp.float32)))
    try:
        async def main():
            return [t async for t in eng.generate([1, 2, 3], 4)]
        assert len(asyncio.run(main())) == 4
        stats = eng.stats()
        assert stats["moe_assignments"] == stats["moe_assignments_made"] > 0
        assert np.asarray(stats["moe_load"]).shape == (2, 4)
        assert stats["kv_page_kind"] == "kv"
    finally:
        eng.close()
