"""The KV pools updated in place: carried through the layer scan and donated
by the engine's loop (``ops/paged_attention.py``, ``models/``,
``serve/engine/engine.py``), at tiny widths on the CPU.

The programs give the bits of the plain form they replace (the pools handed
to ``lax.scan`` as ``xs`` and taken back as ``ys``, KV-head-major, each query
head against its own KV head: kept below as the reference); the views for
callers outside the loop leave their arguments alive; ``stats()`` says the
loop's calls came back in their arguments' buffers; and a call that fails
after it was given the pools costs the live requests an error and nothing
more.  The three-result views a readiness check is made of copy nothing and
never have two pools alive; and the ``kv_*`` counters and the decode
dispatch's ``live_tokens`` / ``gathered_tokens`` are a count of the steps.
"""

import asyncio
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import engine_trace
from ray_tpu.models import gpt, llama
from test_engine_prefill_rungs import failing
from test_engine_stored_weights import (BATCH, FAMILIES, build,
                                        program_args)

STEPS = 8


# ----------------------------------------------- the plain xs/ys reference

def _ref_paged_attention(q, k_pages, v_pages, lengths, page_table):
    B, N, H = q.shape
    NKV, _P, page, _H = k_pages.shape
    rep, S = N // NKV, page_table.shape[1] * page
    k = k_pages[:, page_table].reshape(NKV, B, S, H)
    v = v_pages[:, page_table].reshape(NKV, B, S, H)
    qg = q.reshape(B, NKV, rep, H)
    scores = jnp.einsum("bkrh,kbsh->bkrs", qg, k) * (1.0 / np.sqrt(H))
    valid = jnp.arange(S)[None] < lengths[:, None]
    scores = jnp.where(valid[:, None, None],
                       scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bkrs,kbsh->bkrh", probs, v).reshape(B, N, H)


def _ref_append_kv(k_pages, v_pages, k_new, v_new, pos, page_table):
    page = k_pages.shape[2]
    pid = jnp.take_along_axis(page_table, (pos // page)[:, None],
                              axis=1)[:, 0]
    slot = pos % page
    k_new = jnp.swapaxes(k_new, 0, 1).astype(k_pages.dtype)
    v_new = jnp.swapaxes(v_new, 0, 1).astype(v_pages.dtype)
    return (k_pages.at[:, pid, slot].set(k_new),
            v_pages.at[:, pid, slot].set(v_new))


def _ref_prefill_kv(k_pages, v_pages, k_seq, v_seq, length, row):
    page, pos = k_pages.shape[2], jnp.arange(k_seq.shape[1])
    pid = jnp.where(pos < length, row[pos // page], 0)
    slot = pos % page
    return (k_pages.at[:, pid, slot].set(k_seq.astype(k_pages.dtype)),
            v_pages.at[:, pid, slot].set(v_seq.astype(v_pages.dtype)))


def _ref_llama(params, cfg, tokens, at, k_pages, v_pages, page_table, *,
               prefill):
    """``llama_prefill`` (``at`` the length) or ``llama_decode_step`` (``at``
    the positions) as they were: pools [L, NKV, P, page, H] sliced a layer
    at a time by the scan."""
    dt = cfg.dtype
    rep = cfg.num_heads // cfg.num_kv_heads
    layers, experts = llama._scanned_layers(cfg, params)
    x = params["wte"].astype(dt)[tokens]
    if prefill:
        S = tokens.shape[1]
        cos, sin = llama.rope_tables(S, cfg.head_dim, cfg.rope_theta)
        live = (jnp.arange(S) < at)[None]
        eq_q, eq_kv, eq_o = "bsd,dnh->bnsh", "bsd,dcnh->bcnsh", "bnsh,nhd->bsd"
    else:
        cos_t, sin_t = llama.rope_tables(cfg.max_seq_len, cfg.head_dim,
                                         cfg.rope_theta)
        cos, sin = cos_t[at][:, None], sin_t[at][:, None]
        live = at > 0
        eq_q, eq_kv, eq_o = "bd,dnh->bnh", "bd,dcnh->bcnh", "bnh,nhd->bd"

    def body(x, inp):
        p, kp, vp = inp
        h = llama._rms_norm(x, p["ln1"]["scale"], cfg.rms_eps)
        q = jnp.einsum(eq_q, h, p["attn"]["wq"].astype(dt))
        kv = jnp.einsum(eq_kv, h, p["attn"]["wkv"].astype(dt))
        k, v = kv[:, 0], kv[:, 1]
        q, k = llama._qk(cfg, p, q, k, cos, sin)
        if prefill:
            kp, vp = _ref_prefill_kv(kp, vp, k[0], v[0], at, page_table[0])
            o = llama._dense_causal_attention_gqa(q, k, v, rep)
        else:
            kp, vp = _ref_append_kv(kp, vp, k, v, at, page_table)
            o = _ref_paged_attention(q, kp, vp, at + 1, page_table)
        x = x + jnp.einsum(eq_o, o, p["attn"]["wo"].astype(dt))
        h = llama._rms_norm(x, p["ln2"]["scale"], cfg.rms_eps)
        y, _ = llama._ffn(cfg, p, h, live, experts=experts)
        return x + y, (kp, vp)

    x, (k_pages, v_pages) = jax.lax.scan(body, x, (layers, k_pages, v_pages))
    x = llama._rms_norm(x, params["ln_f"]["scale"], cfg.rms_eps)
    if prefill:
        x = x[0, at - 1][None]
    logits = jnp.einsum("bd,dv->bv", x, params["lm_head"].astype(dt))
    return logits.astype(jnp.float32), k_pages, v_pages


def _ref_gpt(params, cfg, tokens, at, k_pages, v_pages, page_table, *,
             prefill):
    """``gpt_prefill`` / ``gpt_decode_step`` as they were."""
    dt = cfg.dtype
    wpe = params["wpe"].astype(dt)
    x = params["wte"].astype(dt)[tokens] + \
        (wpe[:tokens.shape[1]][None] if prefill else wpe[at])
    eq_qkv, eq_o, eq_i, eq_m = (
        ("bsd,dcnh->bcnsh", "bnsh,nhd->bsd", "bsd,dm->bsm", "bsm,md->bsd")
        if prefill else
        ("bd,dcnh->bcnh", "bnh,nhd->bd", "bd,dm->bm", "bm,md->bd"))

    def body(x, inp):
        p, kp, vp = inp
        h = gpt._layer_norm(x, p["ln1"]["scale"], p["ln1"]["bias"])
        qkv = jnp.einsum(eq_qkv, h, p["attn"]["wqkv"].astype(dt))
        q, k, v = qkv[:, 0], qkv[:, 1], qkv[:, 2]
        if prefill:
            kp, vp = _ref_prefill_kv(kp, vp, k[0], v[0], at, page_table[0])
            o = gpt._dense_causal_attention_bnsh(q, k, v)
        else:
            kp, vp = _ref_append_kv(kp, vp, k, v, at, page_table)
            o = _ref_paged_attention(q, kp, vp, at + 1, page_table)
        o = jnp.einsum(eq_o, o, p["attn"]["wo"].astype(dt))
        x = x + o + p["attn"]["bo"].astype(dt)
        h = gpt._layer_norm(x, p["ln2"]["scale"], p["ln2"]["bias"])
        h = jnp.einsum(eq_i, h, p["mlp"]["wi"].astype(dt)) \
            + p["mlp"]["bi"].astype(dt)
        h = jax.nn.gelu(h)
        h = jnp.einsum(eq_m, h, p["mlp"]["wo"].astype(dt)) \
            + p["mlp"]["bo"].astype(dt)
        return x + h, (kp, vp)

    x, (k_pages, v_pages) = jax.lax.scan(
        body, x, (params["layers"], k_pages, v_pages))
    x = gpt._layer_norm(x, params["ln_f"]["scale"], params["ln_f"]["bias"])
    if prefill:
        x = x[0, at - 1][None]
    logits = jnp.einsum("bd,vd->bv", x, params["wte"].astype(dt))
    return logits.astype(jnp.float32), k_pages, v_pages


def head_major(pool, kv_heads):
    """The engine's [L, P, page, NKV*H] pool as the reference's
    [L, NKV, P, page, H]."""
    L, P, page, width = pool.shape
    return np.asarray(pool.astype(jnp.float32)).reshape(
        L, P, page, kv_heads, width // kv_heads).transpose(0, 3, 1, 2, 4)


# ------------------------------------------------------------------- tests

@pytest.mark.parametrize("family", FAMILIES)
def test_carried_pools_give_the_plain_forms_bits(family):
    """Prefill and eight decode positions, slot 0 live: logits and pools."""
    _, engine = build(family)
    try:
        cfg = engine.model_config
        ref = _ref_gpt if family == "gpt" else _ref_llama
        kv_heads = getattr(cfg, "num_kv_heads", cfg.num_heads)
        ref_step = jax.jit(lambda *a, prefill: ref(
            a[0], cfg, *a[1:], prefill=prefill), static_argnames="prefill")
        params, tokens, length, kp, vp, row = program_args(
            engine, "prefill", engine._params)
        want_kp, want_vp = (jnp.asarray(head_major(p, kv_heads), p.dtype)
                            for p in (kp, vp))
        got, kp, vp, *_ = engine._prefill_program(
            params, tokens, length, kp, vp, row)
        want, want_kp, want_vp = ref_step(
            params, tokens, length, want_kp, want_vp, row, prefill=True)
        table = np.zeros((BATCH, engine._maxp), np.int32)
        table[0] = row[0]
        token, pos = (np.zeros((BATCH,), np.int32) for _ in range(2))
        for step in range(STEPS + 1):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
            assert np.abs(np.asarray(got[0])).max() > 0
            for mine, theirs in ((kp, want_kp), (vp, want_vp)):
                np.testing.assert_array_equal(
                    head_major(mine, kv_heads)[:, :, 1:],   # page 0: scratch
                    np.asarray(theirs.astype(jnp.float32))[:, :, 1:])
            if step == STEPS:
                break
            token[0], pos[0] = int(np.argmax(got[0])), int(length) + step
            got, kp, vp, *_ = engine._decode_program(
                params, token, pos, kp, vp, table)
            want, want_kp, want_vp = ref_step(
                params, token, pos, want_kp, want_vp, table, prefill=False)
        assert np.abs(head_major(kp, kv_heads)[:, :, 1:]).max() > 0
    finally:
        engine.close()


def serve(engine, prompt, new=4):
    async def run():
        return [t async for t in engine.generate(prompt, new)]
    return asyncio.run(run())


def test_the_views_leave_their_arguments_alive_and_equal():
    _, engine = build("llama-dense")
    try:
        args = program_args(engine, "prefill", engine._params)
        first = engine._prefill_program(*args)
        again = engine._prefill_program(*args)
        for a, b in zip(first, again):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert np.abs(np.asarray(first[1].astype(jnp.float32))).max() > 0
        for pool in (engine._k_pages, engine._v_pages):
            assert not pool.is_deleted()
            assert not np.asarray(pool.astype(jnp.float32)).any()
        # the decode view on the prefill's results, which stay alive too
        decode = program_args(engine, "decode", engine._params)
        engine._decode_program(*decode)
        assert not decode[3].is_deleted() and not decode[4].is_deleted()
        assert engine._decode(*decode)[0].shape == (BATCH, 97)
        assert len(serve(engine, [3, 1, 4, 1, 5])) == 4
    finally:
        engine.close()


@pytest.mark.parametrize("family", FAMILIES)
def test_stats_say_the_loops_calls_ran_in_place(family):
    _, engine = build(family)
    try:
        assert engine.stats()["kv_pool_in_place"] == {}
        assert engine.stats()["kv_pool_bytes"] == \
            engine._k_pages.nbytes + engine._v_pages.nbytes > 0
        # the views are not the loop: they say nothing
        engine._prefill_program(*program_args(engine, "prefill",
                                              engine._params))
        assert engine.stats()["kv_pool_in_place"] == {}
        assert len(serve(engine, [3, 1, 4, 1, 5])) == 4
        assert engine.stats()["kv_pool_in_place"] == {"prefill": True,
                                                      "decode": True}
    finally:
        engine.close()


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_a_failed_call_costs_its_requests_and_leaves_fresh_pools(
        program, monkeypatch):
    _, engine = build("llama-dense")
    prompt, calls = [3, 1, 4, 1, 5], []
    # the loop's programs: its prefill rung's, and every decode rung's
    ladder = engine._rung_programs if program == "prefill" \
        else engine._decode_programs

    async def ask():
        return [t async for t in engine.generate(prompt, 4)]

    async def scenario():            # one event loop: the engine lives on it
        want = await ask()
        for rung, compiled in list(ladder.items()):
            monkeypatch.setitem(ladder, rung,
                                failing(compiled.result(), calls))
        with pytest.raises(RuntimeError, match="fell over"):
            await ask()
        monkeypatch.undo()
        stats = engine.stats()
        assert calls == [1]
        assert stats["retired"]["error"] == 1 and stats["active"] == 0
        assert stats["free_pages"] == engine.config.num_pages - 1
        assert not engine._k_pages.is_deleted()
        assert not engine._v_pages.is_deleted()
        assert await ask() == want

    try:
        asyncio.run(scenario())
    finally:
        engine.close()


def alive():
    """Every live array of this process by ``id``, and held: no id here is
    given out again while the result is."""
    gc.collect()                # engines of earlier tests, in cycles
    return {id(a): a for a in jax.live_arrays()}


def pools_alive(engine, earlier):
    """Live arrays of the engine's pool shape: two is one K and one V.
    ``earlier`` is ``alive()`` from before the test built its engines, and
    is left out: an earlier test's engine that something in this process
    still holds (the worker ran other engines first) is not this test's."""
    kp = engine._k_pages
    return sum(a.shape == kp.shape and a.dtype == kp.dtype
               for i, a in alive().items() if i not in earlier)


@pytest.mark.parametrize("family", ["llama-dense", "llama-experts"])
def test_a_check_made_of_the_consuming_views_holds_one_pool(family):
    """``benchmark/replica.py``'s ``check_numerics``: ``_prefill`` on the
    engine's pools, ``_decode`` on what it returned, eight times, and all
    of it again for a second sequence.  The engine's pools are replaced by
    each result (the caller's names alias them), nothing is copied, and the
    engine serves afterwards as one that was never checked."""
    earlier = alive()
    _, engine = build(family)
    _, fresh = build(family)
    try:
        want = serve(fresh, [3, 1, 4, 1, 5])
        fresh.close()
        del fresh
        assert pools_alive(engine, earlier) == 2
        for _ in range(2):
            params, tokens, length, _, _, row = program_args(
                engine, "prefill", engine._params)
            # program_args' copy is gone
            assert pools_alive(engine, earlier) == 2
            before = engine._k_pages
            logits, kp, vp = engine._prefill(
                params, tokens, length, engine._k_pages, engine._v_pages,
                row)
            assert before.is_deleted() and pools_alive(engine, earlier) == 2
            assert kp is engine._k_pages and vp is engine._v_pages
            table = np.zeros((BATCH, engine._maxp), np.int32)
            table[0] = row[0]
            token, pos = (np.zeros((BATCH,), np.int32) for _ in range(2))
            for step in range(STEPS):
                token[0], pos[0] = int(np.argmax(logits[0])), 11 + step
                before = kp
                logits, kp, vp = engine._decode(params, token, pos, kp, vp,
                                                table)
                assert before.is_deleted()
                assert pools_alive(engine, earlier) == 2
                assert kp is engine._k_pages and vp is engine._v_pages
                assert len((logits, kp, vp)) == 3
            del kp, vp, before
        # the pages the check used hold its keys; no sequence reads them
        assert np.asarray(engine._k_pages.astype(jnp.float32)).any()
        assert serve(engine, [3, 1, 4, 1, 5]) == want
        assert engine.stats()["kv_pool_in_place"] == {"prefill": True,
                                                      "decode": True}
        # the copying views still leave what they are given alive
        args = program_args(engine, "prefill", engine._params)
        engine._prefill_program(*args)
        assert not engine._k_pages.is_deleted()
        # and on pools that are not the engine's the consuming views consume
        # them and leave the engine's alone
        decode = program_args(engine, "decode", engine._params)
        mine = engine._k_pages
        engine._decode(*decode)
        assert decode[3].is_deleted() and decode[4].is_deleted()
        assert engine._k_pages is mine and not mine.is_deleted()
    finally:
        engine.close()


def test_a_consuming_view_that_fails_leaves_the_engine_fresh_pools(
        monkeypatch):
    _, engine = build("llama-dense")
    try:
        real = engine._prefill_donating

        def failing(*args):
            real(*args)
            raise RuntimeError("the device fell over")
        monkeypatch.setattr(engine, "_prefill_donating", failing)
        args = program_args(engine, "prefill", engine._params)
        with pytest.raises(RuntimeError, match="fell over"):
            engine._prefill(*args)
        monkeypatch.undo()
        assert not engine._k_pages.is_deleted()
        assert not np.asarray(engine._k_pages.astype(jnp.float32)).any()
        assert len(serve(engine, [3, 1, 4, 1, 5])) == 4
    finally:
        engine.close()


def test_the_kv_counters_and_the_dispatch_attributes_count_the_steps():
    """``engine_trace``'s run: a warm-up sequence, then two sequences under
    the profiler.  Every decode step's paged read gathers, for every slot,
    the pages of the step's rung (``max_batch x W x page``: the least rung
    of ``decode_rungs`` that holds the page the longest live sequence
    writes); of use are the positions the live sequences hold once the
    step's token is written, ``pos + 1``."""
    from jax.profiler import ProfileData
    from ray_tpu.serve.engine.engine import decode_rungs, rung_for
    run = engine_trace.run()
    stats = run["stats"]
    page, maxp = 8, (engine_trace.MAX_PROMPT_LEN + 8) // 8
    rungs = decode_rungs(maxp)
    assert rungs == (1, 2, 3)

    def live(prompts, new, joins):
        """Per step, the positions the live sequences hold (a sequence's
        first token comes from its prefill, each later one from a step;
        ``joins`` is the step each sequence's first one is), and the width
        in pages of the step's table."""
        last = max(j + n - 1 for j, n in zip(joins, new))
        steps = np.zeros((last,), np.int64)
        longest = np.zeros((last,), np.int64)
        for prompt, n, j in zip(prompts, new, joins):
            held = len(prompt) + 1 + np.arange(n - 1)
            steps[j:j + n - 1] += held
            longest[j:j + n - 1] = np.maximum(longest[j:j + n - 1], held)
        # the step writes at the last of the positions it then holds
        return steps, [rung_for(rungs, (n - 1) // page + 1) for n in longest]

    warm, warm_widths = live([engine_trace.WARM_PROMPT],
                             [engine_trace.WARM_NEW], [0])
    # the second prompt arrives while the first sequence's first step runs
    traced, widths = live(engine_trace.PROMPTS, engine_trace.NEW_TOKENS,
                          [0, 1])
    assert warm_widths == [1] and widths == [1, 1, 1, 2, 2]
    gathered = [engine_trace.MAX_BATCH * w * page for w in widths]
    assert stats["steps"] == len(warm) + len(traced)
    assert stats["decode_shapes"] == {1: 4, 2: 2, 3: 0}
    assert stats["kv_live_token_steps"] == warm.sum() + traced.sum()
    assert stats["kv_gathered_token_steps"] == \
        engine_trace.MAX_BATCH * page + sum(gathered)
    # GPT-2 tiny: 2 layers of 4 heads of 8, f32
    assert stats["kv_pool_layers"] == 2
    assert stats["kv_bytes_per_token"] == 2 * 2 * 32 * 4
    assert stats["kv_bytes_per_token"] * 32 * page == stats["kv_pool_bytes"]
    plane, = [p for p in ProfileData.from_file(run["path"]).planes
              if p.name == "/host:CPU"]
    dispatches = sorted(
        (e.start_ns, dict(e.stats)) for line in plane.lines
        for e in line.events if e.name == "rt:engine.decode.dispatch")
    assert [d["live_tokens"] for _, d in dispatches] == list(traced)
    assert [d["gathered_tokens"] for _, d in dispatches] == gathered
    assert [d["width_pages"] for _, d in dispatches] == widths


def test_a_looped_models_pool_counts_a_layer_for_every_pass():
    import dataclasses
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from test_engine_stored_weights import LLAMA, NEW, PAGE, PROMPT
    cfg = dataclasses.replace(LLAMA, ut_steps=3, post_norm=True)
    engine = InferenceEngine(EngineConfig(
        model="llama", model_config=cfg, page_size=PAGE,
        num_pages=BATCH * (PROMPT + NEW) // PAGE + 1, max_batch=BATCH,
        max_prompt_len=PROMPT, max_new_tokens=NEW))
    try:
        stats = engine.stats()
        assert stats["kv_pool_layers"] == 3 * cfg.num_layers
        assert stats["kv_bytes_per_token"] == \
            3 * cfg.num_layers * 2 * cfg.num_kv_heads * cfg.head_dim * 2
        assert len(serve(engine, [3, 1, 4, 1, 5])) == 4
        assert engine.stats()["kv_pool_in_place"] == {"prefill": True,
                                                      "decode": True}
    finally:
        engine.close()
