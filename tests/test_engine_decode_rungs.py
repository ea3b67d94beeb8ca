"""A decode step whose page table is as wide as the batch's longest live
sequence needs (``serve/engine/engine.py``), at tiny widths on the CPU.

The ladder follows from ``maxp`` alone; a step at a narrow rung gives the top
rung's logits and pools (what the narrower table leaves out is positions the
step masks to a weight of exactly 0), for K/V pages, latent pages and a
looped model's pool; the loop's greedy tokens across rung edges are those of
an engine with the one top rung; the rung falls again when a long sequence
retires; every rung's program is there before the first admission, so no
later step compiles; and the counters say which rungs ran.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve.engine import EngineConfig, InferenceEngine
from ray_tpu.serve.engine import engine as engine_module
from ray_tpu.serve.engine.engine import decode_rungs, rung_for
from test_engine_prefill_rungs import FAMILIES, LLAMA, failing, prompt_of

# 32 + 40 positions are nine pages: rungs three pages apart
PAGE, PROMPT, NEW, BATCH = 8, 32, 40, 2
MAXP, RUNGS = 9, (3, 6, 9)
LATENT = dataclasses.replace(
    LLAMA, kv_lora_rank=16, q_lora_rank=24, qk_nope_dim=8, qk_rope_dim=4,
    v_head_dim=6, num_kv_heads=4, rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0))
KINDS = {"kv": FAMILIES["llama-dense"][1], "latent": LATENT,
         "looped": FAMILIES["llama-looped"][1]}


def build(kind="kv", batch=BATCH, prompt=PROMPT, new=NEW):
    cfg = dataclasses.replace(KINDS[kind], max_seq_len=prompt + new)
    maxp = -(-(prompt + new) // PAGE)
    return InferenceEngine(
        EngineConfig(model="llama", model_config=cfg, page_size=PAGE,
                     num_pages=batch * maxp + 1, max_batch=batch,
                     max_prompt_len=prompt, max_new_tokens=new),
        params=llama_init(jax.random.PRNGKey(3), cfg))


def one_rung(monkeypatch):
    """Engines built from here on have the top rung alone."""
    monkeypatch.setattr(engine_module, "decode_rungs",
                        lambda maxp: (maxp,))


# ------------------------------------------------------------- the ladder

@pytest.mark.parametrize("maxp, want", [
    (256, (64, 128, 192, 256)),         # Xing's cell
    (160, (40, 80, 120, 160)),          # Mistral's two
    (96, (24, 48, 72, 96)),             # OLMoE's
    (20, (5, 10, 15, 20)),              # Ouro's
    (3, (1, 2, 3)), (1, (1,)), (2, (1, 2)), (4, (1, 2, 3, 4)),
    # a width that four does not divide: the last step is the shorter
    (5, (2, 4, 5)), (33, (9, 18, 27, 33)), (9, RUNGS)])
def test_the_ladder_follows_from_the_reservations_width_alone(maxp, want):
    rungs = decode_rungs(maxp)
    assert rungs == want
    assert rungs[-1] == maxp and len(rungs) <= 4
    assert all(a < b for a, b in zip(rungs, rungs[1:]))
    # a batch takes the least rung that holds its pages: every count, and
    # so every rung's edge from both sides
    for pages in range(1, maxp + 1):
        assert rung_for(rungs, pages) == min(r for r in rungs if r >= pages)


def test_the_engines_ladder_is_its_maxps():
    engine = build()
    try:
        assert engine._maxp == MAXP and engine._decode_rungs == RUNGS
        assert set(engine._decode_programs) == set(RUNGS)
        assert engine.stats()["decode_shapes"] == dict.fromkeys(RUNGS, 0)
        assert "decode_rungs" not in {
            f.name for f in dataclasses.fields(EngineConfig)}
    finally:
        engine.close()


# ------------------------------------- a narrow rung's step is the top's

@pytest.mark.parametrize("kind", KINDS)
def test_a_step_at_a_narrow_rung_gives_the_top_rungs_logits_and_pools(kind):
    """Two live slots and an idle one, the longer sequence writing the last
    position of its rung's last page: the loop's own compiled programs."""
    engine = build(kind, batch=3)
    try:
        held = (3 * PAGE - 1, 11)       # pos: the 24th position, the 12th
        table = np.zeros((3, MAXP), np.int32)
        table[0] = np.arange(1, MAXP + 1)
        table[1] = np.arange(MAXP + 1, 2 * MAXP + 1)
        padded = np.zeros((1, PROMPT), np.int32)
        kp, vp = engine._k_pages, engine._v_pages
        for slot, n in enumerate(held):
            padded[0, :n] = prompt_of(n)
            _, kp, vp = engine._prefill(engine._params, padded, np.int32(n),
                                        kp, vp, table[slot:slot + 1])
        token = np.array([5, 9, 0], np.int32)
        pos = np.array([*held, 0], np.int32)
        narrow = rung_for(RUNGS, held[0] // PAGE + 1)
        assert narrow == 3 < MAXP
        before = [np.asarray(p) for p in (kp, vp) if p is not None]
        got = {}
        for width in (narrow, MAXP):
            program = engine._decode_programs[width].result()
            logits, k, v, *_ = program(
                engine._params, token, pos, *jax.tree.map(jnp.copy, (kp, vp)),
                np.ascontiguousarray(table[:, :width]))
            got[width] = (np.asarray(logits), [
                np.asarray(p) for p in (k, v) if p is not None])
        np.testing.assert_allclose(got[narrow][0][:2], got[MAXP][0][:2],
                                   rtol=0, atol=2e-6)
        assert np.abs(got[MAXP][0][:2]).max() > 1e-2
        for was, at_rung, at_top in zip(before, got[narrow][1], got[MAXP][1]):
            # the first layer's appended rows come from the embedding alone
            # and are the same bits; a deeper layer's come from attention
            # outputs, sums over a shorter axis: equal to f32's rounding
            np.testing.assert_array_equal(at_rung[0], at_top[0])
            np.testing.assert_allclose(at_rung, at_top, rtol=0, atol=1e-6)
            # the appended rows are in the sequences' own pages (the idle
            # slot's on scratch page 0) and nothing else is touched
            for after in (at_rung, at_top):
                changed = {int(p) for p in np.argwhere(
                    (was != after).reshape(*was.shape[:2], -1).any(-1))[:, 1]}
                assert changed - {0} == {int(table[0, 2]), int(table[1, 1])}
    finally:
        engine.close()


@pytest.mark.parametrize("kind", KINDS)
def test_greedy_tokens_across_two_rung_edges_are_the_one_rung_engines(
        kind, monkeypatch):
    """A prompt of 11 that decodes 40 tokens appends at 11 ... 49: the table
    widens from three pages to six at position 24, on a page boundary as
    every edge is, and to nine at 48.  A step that parked the appended
    position on scratch page 0 would lose that row and change every later
    token."""
    prompts, new = [prompt_of(11), prompt_of(5)], 40

    def serve(engine):
        async def run():
            async def one(p):
                return [t async for t in engine.generate(p, new)]
            return await asyncio.gather(*map(one, prompts))
        try:
            return asyncio.run(run()), engine.stats()
        finally:
            engine.close()

    got, stats = serve(build(kind))
    # both live: positions 11 ... 49 of the longer one, a step each
    assert stats["decode_shapes"] == {3: 13, 6: 24, 9: 2}
    assert stats["steps"] == new - 1
    one_rung(monkeypatch)
    want, stats = serve(build(kind))
    assert stats["decode_shapes"] == {MAXP: new - 1}
    assert got == want
    assert all(len(tokens) == new for tokens in got)


# ------------------------------- the rung follows the batch down as well

def test_the_rung_falls_when_a_long_sequence_retires_and_callers_wait():
    """Four callers for two slots: the long one (a prompt of 30, 20 new)
    holds the rung at six pages and then nine until it retires; the short
    ones beside and after it run at three."""
    engine = build()
    asked = [(prompt_of(30), 20), (prompt_of(3), 8), (prompt_of(4), 6),
             (prompt_of(5), 12)]
    widths = []
    real = engine._decode_inputs

    def watched(stepped):
        batch = real(stepped)
        widths.append(batch[2].shape[1])
        return batch
    engine._decode_inputs = watched

    async def run():
        async def one(p, n):
            return [t async for t in engine.generate(p, n)]
        return await asyncio.gather(*(one(p, n) for p, n in asked))
    try:
        got = asyncio.run(run())
        stats = engine.stats()
    finally:
        engine.close()
    assert [len(t) for t in got] == [n for _, n in asked]
    assert stats["retired"]["done"] == 4 and stats["free_pages"] == 2 * MAXP
    # the long sequence appends at 30 ... 48: six pages, nine from 48; once
    # it is gone, positions under 24
    assert widths[:18] == [6] * 18 and widths[18] == 9
    assert len(widths) > 19 and set(widths[19:]) == {3}
    assert stats["decode_shapes"] == {3: len(widths) - 19, 6: 18, 9: 1}
    assert sum(stats["decode_shapes"].values()) == stats["steps"] \
        == len(widths)
    assert stats["kv_gathered_token_steps"] == sum(
        BATCH * w * PAGE for w in widths)
    assert 0 < stats["kv_live_token_steps"] < stats["kv_gathered_token_steps"]


# ------------------------------------------- no step meets a compile

def test_nothing_compiles_after_the_first_admission_while_rungs_change():
    engine = build()

    def compiled():
        return (engine.stats()["first_call_s"],
                engine._prefill_donating._cache_size(),
                engine._decode_donating._cache_size())

    async def run():   # one event loop an engine
        async def one(n, new):
            return [t async for t in engine.generate(prompt_of(n), new)]
        await one(3, 2)
        first = compiled()
        for n, new in ((3, 40), (32, 40), (17, 2)):
            assert len(await one(n, new)) == new
        return first
    try:
        first = asyncio.run(run())
        assert set(first[0]) == {f"prefill@{PROMPT}"} | {
            f"decode@{w}" for w in RUNGS}
        assert all(seconds > 0 for seconds in first[0].values())
        # the jitted functions were never called: the loop's programs are
        # the rungs', compiled at construction
        assert first[1:] == (0, 0)
        assert compiled() == first
        stats = engine.stats()
        assert all(stats["decode_shapes"][w] > 0 for w in RUNGS)
        assert sum(stats["decode_shapes"].values()) == stats["steps"]
        assert stats["kv_gathered_token_steps"] == sum(
            BATCH * w * PAGE * n for w, n in stats["decode_shapes"].items())
        assert stats["kv_pool_in_place"] == {"prefill": True, "decode": True}
    finally:
        engine.close()


def test_a_rung_that_fails_to_compile_fails_the_steps_that_need_it(
        monkeypatch):
    real = InferenceEngine._compile_decode_rung

    def compile_decode_rung(self, width, *shapes):
        if width == 6:
            raise RuntimeError("the compiler refused this width")
        return real(self, width, *shapes)
    monkeypatch.setattr(InferenceEngine, "_compile_decode_rung",
                        compile_decode_rung)
    engine = build()
    try:
        async def run():
            short = [t async for t in engine.generate(prompt_of(5), 4)]
            with pytest.raises(RuntimeError, match="refused this width"):
                # appends at 22, 23 and then 24: the fourth page
                [t async for t in engine.generate(prompt_of(22), 8)]
            again = [t async for t in engine.generate(prompt_of(5), 4)]
            return short, again
        short, again = asyncio.run(run())
        assert short == again and len(short) == 4
        stats = engine.stats()
        assert stats["retired"]["error"] == 1 and stats["active"] == 0
        assert stats["free_pages"] == BATCH * MAXP
        # the step never ran, so the pools were never given away
        assert not any(p.is_deleted() for p in engine._pools())
        assert stats["decode_shapes"][6] == 0
    finally:
        engine.close()


def test_a_rungs_program_that_fails_leaves_fresh_pools():
    """A failure inside a narrow rung's call, after it was given the pools:
    its sequences get the error and the engine makes fresh pools."""
    engine = build()

    async def run():
        want = [t async for t in engine.generate(prompt_of(5), 4)]
        kept = engine._decode_programs[3]
        engine._decode_programs[3] = failing(kept.result())
        with pytest.raises(RuntimeError, match="fell over"):
            [t async for t in engine.generate(prompt_of(5), 4)]
        engine._decode_programs[3] = kept
        assert not any(p.is_deleted() for p in engine._pools())
        assert not any(np.asarray(p).any() for p in engine._pools())
        assert [t async for t in engine.generate(prompt_of(5), 4)] == want
    try:
        asyncio.run(run())
        assert engine.stats()["retired"]["error"] == 1
    finally:
        engine.close()
