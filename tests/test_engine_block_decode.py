"""A model that generates by diffusion over blocks through
``InferenceEngine`` (ISSUE 40): a decode step denoises a block of positions a
sequence, the blocks' state feeds back on the device from step N to N + 1
while the host fetches step N's ``(committed, tokens)``, a block reaches its
caller when it is whole, and a request for ``n`` tokens streams exactly
``n``.  A tiny float32 engine on the CPU; what it streams is held to the
plain reference's published loop (``benchmark/reference/sdar.py``).

One engine serves every scenario on one event loop (an engine's loop task
lives on the loop of its first ``generate()``), so counters are read as
growth over a scenario.
"""

import asyncio
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
for path in (os.path.dirname(HERE), os.path.join(HERE, "benchmark")):
    if path not in sys.path:
        sys.path.insert(0, path)

import tiny_sdar   # noqa: E402

from ray_tpu.serve import resilience   # noqa: E402
from ray_tpu.serve.engine import EngineConfig, InferenceEngine   # noqa: E402
from ray_tpu.util import tracing   # noqa: E402

B = 4
ENGINE = {"page_size": 8, "max_prompt_len": 32, "max_new_tokens": 24,
          "max_batch": 3, "num_pages": 3 * 7 + 1}
CONFIG = {**tiny_sdar.TINY_SDAR, "engine": ENGINE}
COUNTERS = ("steps", "decode_ahead_steps", "slot_steps", "stray_slot_steps",
            "admitted")


def prompt_of(length, seed=0):
    return [int(t) for t in np.random.default_rng(seed).integers(
        0, 255, length)]


class Served:
    def __init__(self, **engine):
        self.family, self.model, self.params = tiny_sdar.program(CONFIG)
        self.engine = InferenceEngine(EngineConfig(
            model="llama", model_config=self.model, **{**ENGINE, **engine}),
            params=self.params)
        self.loop = asyncio.new_event_loop()

    def want(self, prompt, n):
        return self.family.reference_generate(self.params, prompt, n, CONFIG)

    def run(self, scenario):
        """``scenario(engine)``'s result and what the counters grew by (the
        engine idle before and after, every page back)."""
        before = self.engine.stats()
        async def settled():
            result = await scenario(self.engine)
            # a step in flight when the last stream ended is still to be
            # drained: the engine's loop clears its wake-up when it parks
            while self.engine._wake.is_set():
                await asyncio.sleep(0.005)
            return result
        result = self.loop.run_until_complete(
            asyncio.wait_for(settled(), 120))
        after = self.engine.stats()
        assert after["active"] == after["waiting"] == 0
        assert self.engine._flight is None
        assert after["free_pages"] == self.engine.config.num_pages - 1
        grown = {k: after[k] - before[k] for k in COUNTERS}
        grown["retired"] = {k: after["retired"][k] - before["retired"][k]
                            for k in after["retired"]}
        block = {}
        for k, v in after["block"].items():
            was = before["block"][k]
            block[k] = {t: v[t] - was[t] for t in v} \
                if isinstance(v, dict) else v - was
        grown["block"] = block
        return result, grown

    def close(self):
        self.engine.close()
        self.loop.run_until_complete(asyncio.sleep(0))
        self.loop.close()


async def collect(engine, prompt, n, **kw):
    return [t async for t in engine.generate(prompt, n, **kw)]


def adds_up(grown, delivered):
    """What ``stats()["block"]`` has to add up to over a scenario in which
    ``delivered`` tokens reached callers."""
    block = grown["block"]
    assert block["tokens_committed"] == delivered \
        + block["tokens_dropped_tail"] + block["tokens_dropped_stray"]
    assert sum(block["denoise_passes_by_count"].values()) == \
        block["blocks_committed"] == block["slot_steps_commit"]
    assert grown["slot_steps"] == block["slot_steps_denoise"] \
        + block["slot_steps_commit"] + grown["stray_slot_steps"]
    assert block["unmasked_by_count"] + block["unmasked_by_threshold"] == \
        block["tokens_committed"] - block["tokens_dropped_stray"] \
        or grown["stray_slot_steps"]


@pytest.fixture(scope="module")
def served():
    s = Served()
    yield s
    s.close()


def test_a_request_streams_exactly_what_it_asked_for(served):
    """Lengths that are and are not whole blocks, a prompt under a block,
    an answer of one token; each token its own item, in order."""
    asked = [(prompt_of(12, 1), 9), (prompt_of(13, 2), 8),
             (prompt_of(3, 3), 1), (prompt_of(31, 4), 24)]
    for prompt, n in asked:
        got, grown = served.run(lambda e: collect(e, prompt, n))
        assert got == served.want(prompt, n) and len(got) == n
        assert all(isinstance(t, int) for t in got)
        adds_up(grown, n)
        first = B - len(prompt) % B
        blocks = 1 + -(-max(n - first, 0) // B)
        assert grown["block"]["blocks_committed"] == blocks
        assert grown["block"]["tokens_dropped_tail"] == \
            first + (blocks - 1) * B - n
        assert grown["stray_slot_steps"] == 0
        # a sequence alone: every step but the first of a batch rides
        # behind the one before, and the last block's commit is foreseen
        assert grown["steps"] == grown["slot_steps"]
        assert grown["decode_ahead_steps"] == grown["steps"] - 1


def test_sequences_at_different_passes_share_a_step(served):
    """Three sequences whose first blocks hold 0, 1 and 3 prompt tokens:
    their blocks are whole after 4, 3 and 1 denoise passes, so one step
    commits some slots and denoises others, and every stream is its own."""
    asked = [(prompt_of(8, 5), 12), (prompt_of(9, 6), 11),
             (prompt_of(11, 7), 13)]

    async def scenario(engine):
        return await asyncio.gather(*(collect(engine, p, n)
                                      for p, n in asked))
    got, grown = served.run(scenario)
    for (prompt, n), tokens in zip(asked, got):
        assert tokens == served.want(prompt, n)
    adds_up(grown, sum(n for _, n in asked))
    assert grown["block"]["denoise_passes_by_count"][1] >= 1
    assert grown["block"]["denoise_passes_by_count"][3] >= 1
    # fewer steps than slot steps: the slots shared them
    assert grown["steps"] < grown["slot_steps"] <= 3 * grown["steps"]


def test_more_callers_than_slots_are_admitted_as_slots_free(served):
    asked = [(prompt_of(5 + 3 * i, 10 + i), 5 + 2 * i) for i in range(7)]

    async def scenario(engine):
        return await asyncio.gather(*(collect(engine, p, n)
                                      for p, n in asked))
    got, grown = served.run(scenario)
    for (prompt, n), tokens in zip(asked, got):
        assert tokens == served.want(prompt, n)
    assert grown["admitted"] == 7 and grown["retired"]["done"] == 7
    adds_up(grown, sum(n for _, n in asked))
    # an admission drains the pipe; between admissions the loop is ahead
    assert 0 < grown["decode_ahead_steps"] < grown["steps"]


def test_an_eos_inside_a_block_ends_the_stream_there():
    """The token the model gives second in its answer is made the
    ``eos_token``: the stream ends with it, the rest of its block reaches
    nobody, and the step already in flight is a stray one."""
    probe = Served()
    prompt = prompt_of(12, 21)
    try:
        full = probe.want(prompt, 12)
    finally:
        probe.close()
    eos = full[1]
    assert eos not in full[:1]
    served = Served(eos_token=eos)
    try:
        got, grown = served.run(lambda e: collect(e, prompt, 12))
        assert got == full[:2]
        assert grown["block"]["tokens_dropped_tail"] == 2
        assert grown["stray_slot_steps"] == 1
        adds_up(grown, 2)
        # the pages serve the next caller
        again, _ = served.run(lambda e: collect(e, prompt_of(9, 22), 7))
        assert len(again) <= 7 and again == served.want(
            prompt_of(9, 22), 7)[:len(again)]
    finally:
        served.close()


def test_a_cancelled_stream_frees_its_slot_mid_block(served):
    """The caller walks away after its first block: the sequence is retired
    at the next step boundary, whatever pass its block is at, and the other
    stream is untouched."""
    keep, gone = (prompt_of(10, 31), 16), (prompt_of(12, 32), 24)

    async def scenario(engine):
        async def leaves():
            out = []
            async for t in engine.generate(*gone):
                out.append(t)
                if len(out) == 4:
                    break
            return out
        return await asyncio.gather(collect(engine, *keep), leaves())
    (kept, left), grown = served.run(scenario)
    assert kept == served.want(*keep)
    assert left == served.want(*gone)[:4]
    assert grown["retired"] == {"done": 1, "cancelled": 1, "expired": 0,
                                "error": 0}


def test_a_deadline_retires_a_sequence_between_blocks(served):
    async def scenario(engine):
        with pytest.raises(resilience.DeadlineExceeded):
            await collect(engine, prompt_of(8, 41), 24,
                          deadline=time.time() + 0.0)
        return await collect(engine, prompt_of(8, 42), 6)
    got, grown = served.run(scenario)
    assert got == served.want(prompt_of(8, 42), 6)


def test_a_resumed_stream_continues_bit_identically(served):
    """What the ingress does after a replica is lost: the prompt and the
    tokens delivered so far go to another replica as its prompt.  Delivery
    ends on a block's boundary (a block reaches its caller whole), so the
    resumed request's blocks are the original's and so are its tokens."""
    prompt, n = prompt_of(10, 51), 18
    full, _ = served.run(lambda e: collect(e, prompt, n))
    assert full == served.want(prompt, n)
    for delivered in (2, 6, 14):             # 10 + delivered: whole blocks
        assert (len(prompt) + delivered) % B == 0
        rest, _ = served.run(lambda e: collect(
            e, prompt + full[:delivered], n - delivered))
        assert rest == full[delivered:]


def test_the_regions_say_what_a_step_held_and_yielded(served, tmp_path):
    """Under a profiler session: ``block_len`` beside ``live_tokens`` on the
    dispatch, ``tokens`` / ``dropped_tail`` / ``denoise_slots`` /
    ``commit_slots`` on the delivery, and the static schedule's 0.8 tokens a
    slot step less the dropped tail."""
    import jax
    from benchmark import host_regions
    prompt, n = prompt_of(8, 61), 14
    jax.profiler.start_trace(str(tmp_path))
    try:
        got, grown = served.run(lambda e: collect(e, prompt, n))
    finally:
        jax.profiler.stop_trace()
    assert len(got) == n
    from benchmark import replica
    prof = host_regions.read_profile(replica.find_xplane(str(tmp_path)))
    by = {}
    for name, _, _, attrs in prof["regions"]:
        by.setdefault(name, []).append(attrs)
    dispatches = by["rt:engine.decode.dispatch"]
    assert len(dispatches) == grown["steps"] == 20     # 4 blocks x (4 + 1)
    assert all(d["block_len"] == B and d["active"] == 1 for d in dispatches)
    # positions held: what is committed and the block
    assert [d["live_tokens"] for d in dispatches] == [
        8 + B * (i // 5) + B for i in range(20)]
    delivers = [d for d in by["rt:engine.deliver"] if "commit_slots" in d]
    assert sum(d["commit_slots"] for d in delivers) == 4
    assert sum(d["denoise_slots"] for d in delivers) == 16
    assert sum(d["tokens"] for d in delivers) == n
    assert sum(d["dropped_tail"] for d in delivers) == 2
    assert sum(d["dropped_stray"] for d in delivers) == 0
    per_slot_step = sum(d["tokens"] for d in delivers) / 20
    assert per_slot_step == pytest.approx(0.8 - 2 / 20)
    assert tracing.recording() is False


def test_a_threshold_that_fires_yields_more_a_slot_step():
    config = tiny_sdar.with_generation(CONFIG, confidence_threshold=0.006)
    family, model, params = tiny_sdar.program(config)
    engine = InferenceEngine(EngineConfig(
        model="llama", model_config=model, **ENGINE), params=params)
    prompt, n = prompt_of(12, 1), 12
    try:
        got = asyncio.run(collect(engine, prompt, n))
        block = engine.stats()["block"]
    finally:
        engine.close()
    assert got == family.reference_generate(params, prompt, n, config)
    assert block["unmasked_by_threshold"] > 0
    steps = block["slot_steps_denoise"] + block["slot_steps_commit"]
    assert n / steps > 0.8


def test_the_engine_refuses_a_page_that_splits_a_block():
    family, model, params = tiny_sdar.program(CONFIG)
    with pytest.raises(ValueError, match="page_size"):
        InferenceEngine(EngineConfig(
            model="llama", model_config=model, page_size=6, num_pages=9,
            max_batch=1, max_prompt_len=30, max_new_tokens=18),
            params=params)
