"""Rows that are nobody's cost the experts nothing (ISSUE 56): where
``moe_dropless`` is told which rows are ``live``, a dead row's assignments
are sorted behind the last group, where the grouped matmuls visit no row,
and add nothing in the combine.  A live row's result is the same bits as
without ``live``; the routed part of a dead row is exactly zero; the
kernel's visits are those of the live rows alone; and the served programs
(prefill with padding, the token step and the block step with idle slots)
still answer what the plain forward pass answers at every live position.
"""

import asyncio
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models.llama import (LlamaConfig, llama_block_step,
                                  llama_decode_step, llama_forward,
                                  llama_init, llama_init_paged_cache,
                                  llama_prefill)
from ray_tpu.ops import grouped_matmul as gm
from ray_tpu.ops.moe import moe_dropless

T, D, M, R, K = 24, 32, 16, 16, 4


def layer(seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    p = {"router": jax.random.normal(ks[0], (D, R)),
         "router_bias": 0.1 * jax.random.normal(ks[3], (R,)),
         "wgu": 0.3 * jax.random.normal(ks[1], (R, 2, D, M)),
         "wd": 0.3 * jax.random.normal(ks[2], (R, M, D))}
    shared = {"wgu": 0.3 * jax.random.normal(ks[4], (2, D, M)),
              "wd": 0.3 * jax.random.normal(ks[5], (M, D))}
    x = jax.random.normal(ks[6], (T, D))
    return p, shared, x


LIVE = {"padding": np.arange(T) < T - 7,           # a prefill's tail
        "idle slots": np.arange(T) % 4 != 1,       # a step's idle slots
        "all dead": np.zeros(T, bool),
        "all live": np.ones(T, bool)}


@pytest.mark.parametrize("live", LIVE.values(), ids=LIVE.keys())
@pytest.mark.parametrize("scoring,with_shared", [
    ("softmax", False), ("sigmoid", False), ("sigmoid", True)])
def test_dead_rows_are_routed_nowhere(live, scoring, with_shared):
    """Live rows bit-equal to the run without ``live``; a dead row is the
    shared expert's part alone (exactly zero without one); ``load`` and its
    sum are the live rows'."""
    p, shared, x = layer()
    kw = dict(top_k=K, scoring=scoring, norm_topk_prob=True,
              routed_scaling=2.446 if scoring == "sigmoid" else 1.0,
              shared=shared if with_shared else None)
    y_all, load_all = moe_dropless(x, p, **kw)
    y, load = moe_dropless(x, p, **kw, live=jnp.asarray(live))
    np.testing.assert_array_equal(np.asarray(y)[live],
                                  np.asarray(y_all)[live])
    assert bool(jnp.isfinite(y).all())
    if with_shared:
        routed, _ = moe_dropless(x, p, **{**kw, "shared": None})
        np.testing.assert_allclose(np.asarray(y)[~live],
                                   np.asarray(y_all - routed)[~live],
                                   atol=1e-6)
    else:
        np.testing.assert_array_equal(np.asarray(y)[~live], 0.0)
    assert int(load.sum()) == int(live.sum()) * K
    _, load_live = moe_dropless(x[live], p, **kw) if live.any() \
        else (None, jnp.zeros(R, jnp.int32))
    np.testing.assert_array_equal(np.asarray(load), np.asarray(load_live))
    assert int(load_all.sum()) == T * K


@pytest.mark.parametrize("shares", [4, 2])
def test_a_dead_row_of_a_held_share_is_neither_kept_nor_counted(shares):
    """``kept & live`` under ``first_expert``: each share computes the live
    rows' assignments that fell on ITS experts and nothing of a dead row;
    side by side the shares give the uncut layer at the live rows."""
    p, _, x = layer(seed=1)
    live = LIVE["padding"]
    held = R // shares
    kw = dict(top_k=K, scoring="sigmoid", norm_topk_prob=True)
    whole, load = moe_dropless(x, p, **kw, live=jnp.asarray(live))
    parts, loads = zip(*(moe_dropless(
        x, {**p, "wgu": p["wgu"][at * held:(at + 1) * held],
            "wd": p["wd"][at * held:(at + 1) * held]}, **kw,
        live=jnp.asarray(live), first_expert=at * held)
        for at in range(shares)))
    np.testing.assert_allclose(sum(parts), whole, atol=2e-5)
    for part in parts:
        np.testing.assert_array_equal(np.asarray(part)[~live], 0.0)
    np.testing.assert_array_equal(jnp.concatenate(loads), load)
    assert int(load.sum()) == int(live.sum()) * K
    assert all(0 < int(part_load.sum()) < int(load.sum())
               for part_load in loads)


def test_in_a_scan_over_the_layers_each_layer_skips_its_dead_rows():
    """``layer`` traced (a ``lax.scan``'s index) with ``live``: every layer
    reads its own experts out of the stack, the other layers' are poison."""
    layers = 3
    ps = [layer(seed=10 + i)[0] for i in range(layers)]
    stack = jax.tree.map(lambda *a: jnp.stack(a), *ps)
    _, _, x = layer(seed=20)
    live = LIVE["idle slots"]

    @jax.jit
    def scanned(stack, x):
        def body(_, i):
            return None, moe_dropless(x, stack, layer=i, top_k=K,
                                      live=jnp.asarray(live))
        return jax.lax.scan(body, None, jnp.arange(layers))[1]

    ys, loads = scanned(stack, x)
    for i, p in enumerate(ps):
        want, want_load = moe_dropless(x, p, top_k=K)
        np.testing.assert_allclose(np.asarray(ys[i])[live],
                                   np.asarray(want)[live], rtol=1e-5,
                                   atol=1e-6)
        np.testing.assert_array_equal(np.asarray(ys[i])[~live], 0.0)
        assert int(loads[i].sum()) == int(live.sum()) * K \
            < int(want_load.sum())


def visits(sizes, m, tm):
    return int(gm._visits(jnp.asarray(sizes, jnp.int32), -(-m // tm), tm)[3])


@pytest.mark.parametrize("tm", [128, 256])
@pytest.mark.parametrize("rows,groups", [(32768, 128), (16384, 64),
                                         (1024, 64)])
def test_dead_rows_are_in_no_visit(rows, groups, tm):
    """``_visits``' count with a quarter of the rows dead (behind the last
    group, in no group's size) is no more than the count of the live rows
    alone, less than the count with every row live where groups span tiles,
    and what a count of the (group, row tile) pairs here gives."""
    rng = np.random.default_rng(rows + groups)
    def ragged(total):
        return rng.multinomial(total, rng.dirichlet(np.full(groups, 2.0)))
    every, live = ragged(rows), ragged(rows * 3 // 4)
    ends = np.cumsum(live)
    pairs = sum(int(-(-e // tm) - (e - n) // tm)
                for e, n in zip(ends, live) if n)
    assert visits(live, rows, tm) == pairs == visits(live, rows * 3 // 4, tm)
    # (a decode step's few rows a group: every group is one visit anyway)
    assert visits(live, rows, tm) <= visits(every, rows, tm)
    assert rows < 16384 or visits(live, rows, tm) < visits(every, rows, tm)
    # the bound the grid is sized by still holds
    assert visits(every, rows, tm) <= -(-rows // tm) + groups - 1


def test_a_tile_of_128_rows_multiplies_fewer_rows_than_one_of_256():
    """Why the row tile does not grow: a tile that two groups share is
    multiplied whole by both, so over ragged groups of hundreds of rows the
    rows multiplied are (tiles + groups - 1) x tm, and a tile of 256 costs
    the neighbour's rows twice as dearly as one of 128 (LFM2's gate/up at
    the 4,096 rung: 32,768 rows in 128 groups)."""
    rng = np.random.default_rng(56)
    sizes = np.repeat(rng.multinomial(16384, rng.dirichlet(
        np.full(64, 2.0))), 2)
    multiplied = {tm: visits(sizes, 32768, tm) * tm for tm in (128, 256, 512)}
    assert 32768 < multiplied[128] < multiplied[256] < multiplied[512]
    assert multiplied[256] > 1.9 * 32768 and multiplied[128] < 1.55 * 32768


def test_the_chain_of_the_weights_copies_follows_the_groups_with_rows():
    """``_visits``' ``chain``: a group's first visit, the next group with
    rows (G where there is none) and the buffer its weights go to, which
    alternates over the groups WITH rows: a group without any is never
    waited for, sent for or given a buffer of its own."""
    sizes, tm = [5, 0, 130, 1, 0, 64], 128
    G = len(sizes)
    _, group_ids, tile_ids, count, chain = gm._visits(
        jnp.asarray(sizes, jnp.int32), 2, tm)
    first, following, buffer = np.asarray(chain).reshape(3, G)
    assert int(count) == 5
    assert np.asarray(group_ids)[:5].tolist() == [0, 2, 2, 3, 5]
    assert np.asarray(tile_ids)[:5].tolist() == [0, 0, 1, 1, 1]
    with_rows = [0, 2, 3, 5]
    assert first[with_rows].tolist() == [0, 1, 3, 4]
    assert following.tolist() == [2, 2, 3, 5, 5, G]
    assert buffer[with_rows].tolist() == [0, 1, 0, 1]
    # no rows at all: no visit, and nobody to send for
    _, _, _, count, chain = gm._visits(jnp.zeros(G, jnp.int32), 2, tm)
    assert int(count) == 0 and np.asarray(chain)[G:2 * G].tolist() == [G] * G


# ---------------------------------------------------------------- the model

E = 8
CFG = LlamaConfig(vocab_size=97, max_seq_len=48, num_layers=2, num_heads=4,
                  num_kv_heads=4, embed_dim=D, mlp_dim=M, num_experts=E,
                  experts_per_token=3, qk_norm=True, dtype=jnp.float32,
                  attention="dense", remat=False)
PAGE, RUNG, SLOTS = 8, 32, 3
MAXP = CFG.max_seq_len // PAGE


@pytest.fixture(scope="module")
def params():
    p = llama_init(jax.random.PRNGKey(0), CFG)
    p["layers"]["mlp"]["router"] = p["layers"]["mlp"]["router"] * 30
    return p


def pools(cfg=CFG):
    return llama_init_paged_cache(cfg, SLOTS * MAXP + 1, PAGE)


def table():
    return np.arange(1, SLOTS * MAXP + 1, dtype=np.int32).reshape(SLOTS, MAXP)


@pytest.fixture(scope="module")
def prefill():
    return jax.jit(lambda p, *a: llama_prefill(p, CFG, *a))


@pytest.mark.parametrize("length", [9, 25, 32])
def test_a_prefill_of_any_length_in_the_rung_answers_as_the_forward_pass(
        params, prefill, length):
    """Prompts of two lengths (and one that fills the rung) through ONE
    program of the rung: the logits are the plain forward pass's over the
    prompt alone, the pool's live positions are what a rung with no padding
    writes, the load is the prompt's, and the padding was routed nowhere."""
    prompt = np.arange(5, 5 + length, dtype=np.int32)
    toks = np.full((1, RUNG), 60, np.int32)
    toks[0, :length] = prompt
    kp, vp = pools()
    logits, k1, v1, load = prefill(params, toks, np.int32(length), kp, vp,
                                   table()[:1])
    full = llama_forward(params, jnp.asarray(prompt)[None], CFG)
    np.testing.assert_allclose(np.asarray(logits[0]), np.asarray(full[0, -1]),
                               rtol=2e-4, atol=2e-5)
    assert np.asarray(load).sum(1).tolist() == [length * 3] * 2
    # the same prompt with the rung's other positions real: the pages of
    # the live positions hold the same keys and values
    whole = np.arange(5, 5 + RUNG, dtype=np.int32)[None]
    _, k0, v0, load0 = prefill(params, whole, np.int32(RUNG), *pools(),
                               table()[:1])
    for got, want in ((k1, k0), (v1, v0)):
        got = np.asarray(got[:, 1:1 + RUNG // PAGE]).reshape(2, RUNG, -1)
        want = np.asarray(want[:, 1:1 + RUNG // PAGE]).reshape(2, RUNG, -1)
        np.testing.assert_allclose(got[:, :length], want[:, :length],
                                   rtol=1e-5, atol=1e-6)
    assert np.asarray(load0).sum(1).tolist() == [RUNG * 3] * 2


def test_a_token_step_with_idle_slots_answers_as_the_forward_pass(params,
                                                                  prefill):
    prompt = np.arange(3, 14, dtype=np.int32)
    toks = np.zeros((1, RUNG), np.int32)
    toks[0, :len(prompt)] = prompt
    kp, vp = pools()
    _, kp, vp, _ = prefill(params, toks, np.int32(len(prompt)), kp, vp,
                           table()[1:2])
    token, pos = np.zeros(SLOTS, np.int32), np.zeros(SLOTS, np.int32)
    token[1], pos[1] = 21, len(prompt)
    idle = np.zeros((SLOTS, MAXP), np.int32)
    idle[1] = table()[1]
    logits, _, _, load = jax.jit(
        lambda p, *a: llama_decode_step(p, CFG, *a))(
            params, token, pos, kp, vp, idle)
    full = llama_forward(params, jnp.asarray(np.append(prompt, 21))[None],
                         CFG)
    np.testing.assert_allclose(np.asarray(logits[1]), np.asarray(full[0, -1]),
                               rtol=2e-4, atol=2e-5)
    assert np.asarray(load).sum(1).tolist() == [3, 3]
    assert bool(jnp.isfinite(logits).all())


def test_a_block_step_with_parked_slots_answers_as_a_step_of_its_own(params):
    """A live slot's block beside two parked slots against the same block in
    a batch of one: the parked slots' rows went through no expert and the
    live slot's logits are what they are alone."""
    block = dataclasses.replace(CFG, block_length=4, denoise_steps=2,
                                mask_token=96)
    step = jax.jit(lambda p, *a: llama_block_step(p, block, *a))
    tokens = np.full((SLOTS, 4), 96, np.int32)
    tokens[2] = [11, 96, 13, 96]
    pos0, end = np.array([0, 0, 8], np.int32), np.array([0, 0, 16], np.int32)
    def state(rows):
        return (tokens[rows], tokens[rows] == 96, pos0[rows],
                np.zeros(len(rows), np.int32))
    every, alone = np.arange(SLOTS), np.array([2])
    kp, vp = pools(block)
    logits, _, _, load = step(params, state(every), end, kp, vp, table())
    kp, vp = pools(block)
    own, _, _, own_load = step(params, state(alone), end[alone], kp, vp,
                               table()[alone])
    np.testing.assert_allclose(np.asarray(logits[2]), np.asarray(own[0]),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(np.asarray(load), np.asarray(own_load))
    assert np.asarray(load).sum(1).tolist() == [4 * 3] * 2
    assert bool(jnp.isfinite(logits).all())


# --------------------------------------------------------------- the engine

def test_the_engine_counts_the_rows_its_programs_offered(params):
    """``moe_rows_offered``: every row of every call's shape (the prefill's
    rung, the step's slots) times k times the expert layers, beside the
    ``moe_assignments_made`` of the real tokens among them; and the same two
    on the profiler's regions."""
    from ray_tpu.serve.engine import EngineConfig, InferenceEngine
    from ray_tpu.serve.engine import engine as engine_module
    config = EngineConfig(model="llama", model_config=CFG, page_size=PAGE,
                          num_pages=SLOTS * MAXP + 1, max_batch=SLOTS,
                          max_prompt_len=RUNG, max_new_tokens=8)
    prompt, new = [5, 17, 3, 88, 41, 2, 9], 5
    seen = []
    region = engine_module.region

    def recording(name, **attrs):
        if name.endswith(".moe"):
            seen.append((name, attrs))
        return region(name, **attrs)

    async def go():
        engine = InferenceEngine(config, params=params)
        engine_module.region = recording
        try:
            tokens = [t async for t in engine.generate(prompt, new)]
        finally:
            engine_module.region = region
        stats = engine.stats()
        engine.close()
        return tokens, stats

    tokens, stats = asyncio.run(go())
    per_row = 3 * CFG.num_layers
    assert len(tokens) == new and stats["steps"] == new - 1
    rung = max(r for r in stats["prefill_shapes"]
               if stats["prefill_shapes"][r])
    assert stats["moe_assignments_made"] == stats["moe_assignments"] \
        == (len(prompt) + new - 1) * per_row
    assert stats["moe_rows_offered"] == (int(rung) + (new - 1) * SLOTS) \
        * per_row
    by_name = {name: [a for n, a in seen if n == name]
               for name in ("engine.prefill.moe", "engine.decode.moe")}
    assert [a["rows_offered"] for a in by_name["engine.prefill.moe"]] \
        == [int(rung) * per_row]
    assert [a["assignments_made"] for a in by_name["engine.prefill.moe"]] \
        == [len(prompt) * per_row]
    assert [(a["assignments_made"], a["rows_offered"])
            for a in by_name["engine.decode.moe"]] \
        == [(per_row, SLOTS * per_row)] * (new - 1)
