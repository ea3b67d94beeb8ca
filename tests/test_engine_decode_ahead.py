"""The engine's loop one step ahead of its own tokens (ISSUE 38): the next
token chosen inside the decode program and fed back on the device, step N+1
dispatched before step N's tokens are fetched.  Tiny engines on the CPU.

Whatever the order of dispatches and fetches, every stream gets the tokens a
step-by-step greedy decode through the ``_prefill`` / ``_decode`` views
gives; a sequence retired with a step in flight (an ``eos_token``, a
cancellation, a deadline) leaves a stray slot step whose token reaches
nobody and whose pages serve the next admission; no step is dispatched for
a sequence whose last token is coming; a failure with a step in flight
costs every live and waiting caller one error and leaves the engine
serving; and with a device that takes its time, step N+1's dispatch begins
before step N's fetch ends.

An engine of each kind is built once and serves every scenario on one event
loop (an engine's loop task lives on the loop of its first ``generate()``),
so the counters are read as growth over a scenario.
"""

import asyncio
import concurrent.futures
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.gpt import GPTConfig, gpt_init
from ray_tpu.models.llama import LlamaConfig, llama_init
from ray_tpu.serve import resilience
from ray_tpu.serve.engine import EngineConfig, InferenceEngine

PAGE, PROMPT, NEW, BATCH = 8, 16, 12, 3
MAXP = -(-(PROMPT + NEW) // PAGE)
LLAMA = LlamaConfig(vocab_size=97, max_seq_len=PROMPT + NEW, num_layers=2,
                    num_heads=4, num_kv_heads=2, embed_dim=32, mlp_dim=48,
                    dtype=jnp.float32, attention="dense", remat=False)
KINDS = {
    "gpt": ("gpt", GPTConfig(
        vocab_size=97, max_seq_len=PROMPT + NEW, num_layers=2, num_heads=4,
        embed_dim=32, dtype=jnp.float32, attention="dense", remat=False),
        gpt_init),
    "llama-dense": ("llama", LLAMA, llama_init),
    "llama-experts": ("llama", dataclasses.replace(
        LLAMA, num_kv_heads=4, mlp_dim=16, num_experts=8,
        experts_per_token=3, qk_norm=True), llama_init),
    "llama-latent": ("llama", LlamaConfig(
        vocab_size=97, max_seq_len=PROMPT + NEW, num_layers=2, num_heads=4,
        num_kv_heads=4, embed_dim=64, mlp_dim=32, num_experts=8,
        experts_per_token=2, norm_topk_prob=True, kv_lora_rank=32,
        q_lora_rank=48, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=12,
        rope_yarn=(64.0, 16.0, 32.0, 1.0, 1.0, 1.0), first_dense_layers=1, dense_mlp_dim=96, shared_experts=1,
        router_scoring="sigmoid", router_bias=True, routed_scaling=2.0,
        hc_mult=4, dtype=jnp.float32), llama_init),
}


class Served:
    """An engine of one kind, the event loop it lives on, and the greedy
    reference of every (prompt, new) a scenario asks about."""

    def __init__(self, kind):
        model, cfg, init = KINDS[kind]
        # pages for a full batch and no more: a retired sequence's pages
        # are what the next admission gets
        self.engine = InferenceEngine(EngineConfig(
            model=model, model_config=cfg, page_size=PAGE,
            num_pages=BATCH * MAXP + 1, max_batch=BATCH,
            max_prompt_len=PROMPT, max_new_tokens=NEW),
            params=init(jax.random.PRNGKey(3), cfg))
        self.loop = asyncio.new_event_loop()
        self.retired = []          # every sequence the engine retired
        retire = self.engine._retire

        def watched(seq, reason):
            self.retired.append(seq)
            retire(seq, reason)
        self.engine._retire = watched
        self._greedy = {}

    def greedy(self, prompt, new=NEW):
        """``new`` tokens after ``prompt``, one position at a time through
        the three-result views on the engine's own pools (slot 0, pages
        1..maxp), while the loop is idle."""
        key = tuple(prompt)
        if len(self._greedy.get(key, ())) < new:
            eng, cfg = self.engine, self.engine.config
            assert not eng._active and eng._flight is None
            table = np.zeros((cfg.max_batch, eng._maxp), np.int32)
            table[0] = np.arange(1, eng._maxp + 1)
            padded = np.zeros((1, cfg.max_prompt_len), np.int32)
            padded[0, :len(prompt)] = prompt
            logits, kp, vp = eng._prefill(
                eng._params, padded, np.int32(len(prompt)), eng._k_pages,
                eng._v_pages, table[:1])
            out = [int(np.argmax(logits[0]))]
            tok = np.zeros((cfg.max_batch,), np.int32)
            pos = np.zeros((cfg.max_batch,), np.int32)
            for i in range(NEW - 1):
                tok[0], pos[0] = out[-1], len(prompt) + i
                logits, kp, vp = eng._decode(eng._params, tok, pos, kp, vp,
                                             table)
                out.append(int(np.argmax(logits[0])))
            self._greedy[key] = out
        return self._greedy[key][:new]

    def run(self, scenario):
        """``scenario(engine)``'s result, and what the counters grew by
        while it ran (the engine idle before and after)."""
        before = self.engine.stats()
        seen = len(self.retired)
        result = self.loop.run_until_complete(
            asyncio.wait_for(scenario(self.engine), 120))
        after = self.engine.stats()
        assert after["active"] == after["waiting"] == 0
        assert self.engine._flight is None
        assert after["free_pages"] == BATCH * MAXP   # every page came back
        grown = {k: after[k] - before[k] for k in (
            "steps", "decode_ahead_steps", "slot_steps", "stray_slot_steps",
            "admitted")}
        grown["retired"] = {k: after["retired"][k] - before["retired"][k]
                            for k in after["retired"]}
        # no step for a sequence whose last token was coming: a slot step is
        # a token somebody got (its first came from its prefill) or a stray
        assert grown["slot_steps"] == grown["stray_slot_steps"] + sum(
            seq.generated - 1 for seq in self.retired[seen:]
            if seq.prefilled)
        assert 0 <= grown["decode_ahead_steps"] <= grown["steps"]
        return result, grown

    def close(self):
        self.engine.close()
        self.loop.run_until_complete(asyncio.sleep(0))
        self.loop.close()


@pytest.fixture(scope="module", params=list(KINDS))
def served(request):
    s = Served(request.param)
    yield s
    s.close()


def prompt_of(n, salt=0):
    return [int(t) for t in (np.arange(n) * 7 + 3 + 11 * salt) % 97]


def done(value):
    """In place of a compiled rung: a finished future holding ``value``."""
    future = concurrent.futures.Future()
    future.set_result(value)
    return future


async def collect(stream):
    return [t async for t in stream]


async def after_tokens(stream, n):
    """The first ``n`` tokens of ``stream``, taken one at a time."""
    return [await stream.__anext__() for _ in range(n)]


# --------------------------------------- the same tokens, whatever the order

def staggered(s):
    """The second caller arrives at the first's third token, the third at
    its sixth: each admission drains the pipe and fills it again."""
    asks = [(prompt_of(5), 12), (prompt_of(9, 1), 7), (prompt_of(3, 2), 4)]

    async def scenario(engine):
        first = engine.generate(*asks[0])
        head = await after_tokens(first, 3)
        second = asyncio.ensure_future(collect(engine.generate(*asks[1])))
        head += await after_tokens(first, 3)
        third = asyncio.ensure_future(collect(engine.generate(*asks[2])))
        return [head + await collect(first), await second, await third]
    want = [s.greedy(p, n) for p, n in asks]
    got, grown = s.run(scenario)
    assert got == want
    assert grown["retired"]["done"] == 3 and grown["stray_slot_steps"] == 0
    # three stretches that each start on a drained pipe (at least: a stream
    # may wake the loop between two steps), and most steps ahead
    assert grown["steps"] - grown["decode_ahead_steps"] >= 3
    assert grown["decode_ahead_steps"] >= 5


def mixed_max_new(s):
    """More callers than slots, every ``max_new`` from 1 on: a sequence's
    end by count is foreseen, so nothing strays; one that asks for a single
    token takes no decode step at all."""
    asks = [(prompt_of(4 + i, i), n)
            for i, n in enumerate((1, 2, 3, 12, 1, 5, 2))]

    async def scenario(engine):
        return await asyncio.gather(
            *(collect(engine.generate(p, n)) for p, n in asks))
    want = [s.greedy(p, n) for p, n in asks]
    got, grown = s.run(scenario)
    assert got == want and [len(g) for g in got] == [n for _, n in asks]
    assert grown["stray_slot_steps"] == 0
    assert grown["slot_steps"] == sum(n - 1 for _, n in asks)
    assert grown["retired"]["done"] == len(asks)


def single_tokens_only(s):
    """``max_new == 1`` all round: prefills and no decode step."""
    asks = [(prompt_of(3 + i, i), 1) for i in range(4)]

    async def scenario(engine):
        return await asyncio.gather(
            *(collect(engine.generate(p, n)) for p, n in asks))
    want = [s.greedy(p, n) for p, n in asks]
    got, grown = s.run(scenario)
    assert got == want
    assert grown["steps"] == grown["slot_steps"] == 0
    assert grown["admitted"] == 4


def eos_mid_batch(s):
    """A token of the middle of one stream becomes the ``eos_token``: that
    stream ends there with its next step already in flight, a stray; the
    pages it frees serve the caller that was waiting for them."""
    asks = [(prompt_of(4 + k % 5, k), 12) for k in range(3, 16)]
    want = [s.greedy(p, n) for p, n in asks]
    # a token new to its stream at a place where a step is in flight behind
    # it (not a stream's first two, nor its last), beside three other streams
    first, at = next((i, j) for i, seq in enumerate(want)
                     for j in range(2, 10) if seq[j] not in seq[:j])
    eos = want[first][at]
    chosen = [first] + [i for i in range(len(asks)) if i != first][:3]
    asks, want = [asks[i] for i in chosen], [want[i] for i in chosen]

    def cut(seq):
        return seq[:seq.index(eos) + 1] if eos in seq else seq
    want = [cut(seq) for seq in want]

    async def scenario(engine):
        return await asyncio.gather(
            *(collect(engine.generate(p, n)) for p, n in asks))
    s.engine.config.eos_token = eos
    try:
        got, grown = s.run(scenario)
    finally:
        s.engine.config.eos_token = None
    assert got == want
    assert grown["retired"]["done"] == 4
    # a stream cut short of its count had a step behind its last token,
    # unless the pipe was being drained for the waiting caller just then
    cut_short = sum(len(seq) < 12 for seq in want)
    assert 1 <= grown["stray_slot_steps"] <= cut_short


def cancelled_in_flight(s):
    """A caller goes away after three tokens, beside two that stay and one
    that waits for its slot and pages."""
    asks = [(prompt_of(6, 7), 12), (prompt_of(4, 8), 12),
            (prompt_of(7, 9), 10), (prompt_of(5, 10), 9)]
    want = [s.greedy(p, n) for p, n in asks]

    async def scenario(engine):
        leaving = engine.generate(*asks[0])
        others = [asyncio.ensure_future(collect(engine.generate(p, n)))
                  for p, n in asks[1:]]
        head = await after_tokens(leaving, 3)
        await leaving.aclose()
        return [head] + [await o for o in others]
    got, grown = s.run(scenario)
    assert got == [want[0][:3]] + want[1:]
    assert grown["retired"] == {"done": 3, "cancelled": 1, "expired": 0,
                                "error": 0}
    # its fourth token was in flight when it left
    assert grown["stray_slot_steps"] == 1


def deadline_in_flight(s):
    """A deadline passes in the middle of a stream (every decode step made
    to take 20 ms): the caller gets ``DeadlineExceeded`` after a prefix of
    its tokens, the others all of theirs."""
    asks = [(prompt_of(6, 11), 12), (prompt_of(4, 12), 12),
            (prompt_of(7, 13), 12)]
    want = [s.greedy(p, n) for p, n in asks]
    engine = s.engine
    kept = dict(engine._decode_programs)

    def slowed(real):
        def program(*args):
            time.sleep(0.02)
            return real(*args)
        return done(program)

    async def scenario(engine):
        others = [asyncio.ensure_future(collect(engine.generate(p, n)))
                  for p, n in asks[1:]]
        got = []
        with pytest.raises(resilience.DeadlineExceeded):
            async for t in engine.generate(*asks[0],
                                           deadline=time.time() + 0.1):
                got.append(t)
        return [got] + [await o for o in others]
    for width, compiled in kept.items():
        engine._decode_programs[width] = slowed(compiled.result())
    try:
        got, grown = s.run(scenario)
    finally:
        engine._decode_programs.update(kept)
    assert got[1:] == want[1:]
    assert len(got[0]) < 12 and got[0] == want[0][:len(got[0])]
    assert grown["retired"]["done"] == 2
    assert grown["retired"]["expired"] + grown["retired"]["cancelled"] == 1
    assert grown["stray_slot_steps"] <= 1


SCENARIOS = [staggered, mixed_max_new, single_tokens_only, eos_mid_batch,
             cancelled_in_flight, deadline_in_flight]


@pytest.mark.parametrize("scenario", SCENARIOS, ids=lambda f: f.__name__)
def test_every_stream_gets_the_step_by_step_greedy_tokens(served, scenario):
    scenario(served)


# ------------------------------------------------ a failure, a step in flight

@pytest.mark.parametrize("consumed", [False, True],
                         ids=["before-the-pools", "after-the-pools"])
def test_a_failure_with_a_step_in_flight_costs_each_caller_one_error(
        consumed):
    """The fourth decode dispatch raises, before or after its program was
    given the pools, with the third step in flight: the three live callers
    and the two that wait get the error, once each, and the engine serves
    the next caller what it serves on a fresh engine."""
    s = Served("gpt")
    engine = s.engine
    asks = [(prompt_of(4 + i, i), 12) for i in range(5)]
    kept = dict(engine._decode_programs)
    calls = []

    def breaking(real):
        def program(*args):
            calls.append(engine._flight is not None)
            if len(calls) == 4:
                if consumed:
                    real(*args)
                raise RuntimeError("the device fell over")
            return real(*args)
        return done(program)

    async def scenario(engine):
        async def one(p, n):
            got = []
            try:
                async for t in engine.generate(p, n):
                    got.append(t)
            except RuntimeError as e:
                return got, [str(e)]
            return got, []
        return await asyncio.gather(*(one(p, n) for p, n in asks))
    try:
        want = [s.greedy(p, n) for p, n in asks]
        for width, compiled in kept.items():
            engine._decode_programs[width] = breaking(compiled.result())
        got, grown = s.run(scenario)
        engine._decode_programs.update(kept)
        # the failing dispatch had a step in flight behind it
        assert calls == [False, True, True, True]
        for (tokens, errors), full in zip(got, want):
            assert errors == ["the device fell over"]
            assert tokens == full[:len(tokens)]
        # the live three had their prefill's token and two steps' (the third
        # step's were in flight and are nobody's); the waiting two had none
        assert sorted(len(t) for t, _ in got) == [0, 0, 3, 3, 3]
        assert grown["retired"]["error"] == BATCH
        assert not engine._pools_deleted()
        again, _ = s.run(lambda engine: collect(engine.generate(*asks[0])))
        assert again == want[0]
    finally:
        s.close()


# ----------------------------------------------------------- the overlap

class OnDevice:
    """A decode step's next-token array on a device that takes ``STEP_S`` a
    step, one step after the other: there to read when its step ends."""
    STEP_S = 0.05
    busy_until = 0.0

    def __init__(self, real, log):
        self.real, self.log = real, log
        started = max(time.perf_counter(), OnDevice.busy_until)
        OnDevice.busy_until = self.ready_at = started + self.STEP_S

    def copy_to_host_async(self):
        pass

    def __array__(self, dtype=None, copy=None):
        time.sleep(max(0.0, self.ready_at - time.perf_counter()))
        self.log.append(("fetched", time.perf_counter()))
        return np.asarray(self.real)


def test_step_n_plus_1_is_dispatched_before_step_n_is_fetched():
    """A device stub that takes 50 ms a step and returns from its dispatch at
    once: every step but the first is handed over while the step before it
    runs, so the device never waits for the host and six steps take six
    device steps, not six device steps and six round trips."""
    s = Served("gpt")
    engine = s.engine
    kept = dict(engine._decode_programs)
    log = []

    def queued(real):
        def program(params, token, pos, kp, vp, pt):
            log.append(("dispatched", time.perf_counter()))
            if isinstance(token, OnDevice):     # fed back where it lies
                token = token.real
            *rest, nxt = real(params, token, pos, kp, vp, pt)
            return (*rest, OnDevice(nxt, log))
        return done(program)
    try:
        want = s.greedy(prompt_of(5), 7)
        for width, compiled in kept.items():
            engine._decode_programs[width] = queued(compiled.result())
        started = time.perf_counter()
        got, grown = s.run(
            lambda engine: collect(engine.generate(prompt_of(5), 7)))
        took = time.perf_counter() - started
        assert got == want
        assert grown["steps"] == 6 and grown["decode_ahead_steps"] == 5
        dispatched = [t for what, t in log if what == "dispatched"]
        fetched = [t for what, t in log if what == "fetched"]
        assert len(dispatched) == len(fetched) == 6
        for n in range(5):
            # step n+1 reached the device before step n's tokens the host,
            # and a whole device step before its own turn ended
            assert dispatched[n + 1] < fetched[n]
            assert fetched[n + 1] - dispatched[n + 1] > 0.8 * OnDevice.STEP_S
        # back to back on the device: the host's work hides behind it
        assert fetched[-1] - dispatched[0] < 6 * OnDevice.STEP_S + 0.04
        assert took < 6 * OnDevice.STEP_S + 0.25
    finally:
        engine._decode_programs.update(kept)
        s.close()
