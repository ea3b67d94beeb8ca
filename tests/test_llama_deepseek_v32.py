"""DeepSeek-V3.2-Exp's block in ``models/llama.py`` (ISSUE 57): latent
attention behind a lightning indexer (``_index_project``: queries from the
attention's own query bottleneck, one LayerNormed key a position in a pool of
its own, the first ``qk_rope_dim`` columns rotated), the ``index_topk``
positions a query keeps (``ops/paged_attention.py``: a mask a query in a
prompt's chunk, ``lax.top_k`` and a gather of the selected rows in the token
step), a prompt in chunks over the sequence's pages (``_mla_chunk``), and
experts chosen inside groups.  Small widths on the CPU in float32, against
the benchmark's plain reference, which makes none of these the same way."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import deepseek_v32 as reference
from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig

PAGE, SEQ, STEPS = 8, 64, 4
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=SEQ, num_layers=2, num_heads=4, num_kv_heads=4,
    embed_dim=64, mlp_dim=16, dtype=jnp.float32, rms_eps=1e-6,
    num_experts=16, experts_per_token=4, norm_topk_prob=True,
    kv_lora_rank=32, q_lora_rank=24, qk_nope_dim=16, qk_rope_dim=8,
    v_head_dim=16, rope_yarn=(40.0, 16.0, 32.0, 1.0, 1.0, 1.0),
    first_dense_layers=1, dense_mlp_dim=96, shared_experts=1,
    router_scoring="sigmoid", router_bias=True, routed_scaling=2.5,
    index_heads=4, index_head_dim=16, index_topk=8, expert_groups=(4, 2),
    expert_share=(1, 4))
# the reference's reading of the same model, under the published keys
PUBLISHED = {
    "rms_norm_eps": 1e-6, "kv_lora_rank": 32, "qk_nope_head_dim": 16,
    "qk_rope_head_dim": 8, "rope_theta": 10000.0, "index_n_heads": 4,
    "index_head_dim": 16, "index_topk": 8, "n_group": 4, "topk_group": 2,
    "num_experts_per_tok": 4, "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "n_routed_experts": 4,
    "expert_share": [1, 4],
    "rope_scaling": {"factor": 40.0, "original_max_position_embeddings": 16,
                     "beta_fast": 32.0, "beta_slow": 1.0, "mscale": 1.0,
                     "mscale_all_dim": 1.0, "type": "yarn"}}


def build(cfg, seed=1):
    """Seeded weights with every way out eight times the initialisation's
    and the indexer's LayerNorm moved off the identity: at the
    initialisation's scale a 64-wide model's sublayers whisper."""
    tree = llama.llama_init(jax.random.PRNGKey(seed), cfg)

    def louder(path, a):
        name = getattr(path[-1], "key", "")
        if name in ("index_k_norm", "index_k_bias"):
            return a + 0.3 * jax.random.normal(jax.random.PRNGKey(7), a.shape)
        return {"wo": 8.0, "wd": 8.0, "router": 40.0}.get(name, 1.0) * a
    return jax.tree_util.tree_map_with_path(louder, tree)


@functools.lru_cache(maxsize=None)
def programs(cfg):
    """The two programs of ``cfg``, jitted once a configuration."""
    return (jax.jit(lambda p, *a: llama.llama_prefill(p, cfg, *a)),
            jax.jit(lambda p, *a: llama.llama_decode_step(p, cfg, *a)))


def served(cfg, params, tokens, n, chunks, steps=STEPS, pages=None):
    """Prefill ``tokens[:n]`` in ``chunks`` (widths) then ``steps`` token
    steps through the pools: the logits of positions ``n - 1 .. n + steps -
    1``, and the pools."""
    maxp = SEQ // PAGE
    kp, vp = pages or llama.llama_init_paged_cache(cfg, maxp + 3, PAGE)
    table = np.arange(2, maxp + 2, dtype=np.int32)[None]
    prefill, decode = (functools.partial(f, params) for f in programs(cfg))
    start = 0
    for width in chunks:
        rung = -(-width // PAGE) * PAGE
        padded = np.zeros((1, rung), np.int32)
        padded[0, :width] = tokens[start:start + width]
        logits, kp, vp, _ = prefill(padded, np.int32(width), kp, vp, table,
                                    np.int32(0), np.int32(start))
        start += width
    assert start == n
    got = [np.asarray(logits[0])]
    tok, pos = np.zeros((2,), np.int32), np.zeros((2,), np.int32)
    tables = np.zeros((2, maxp), np.int32)
    tables[1] = table[0]                         # slot 1 live, slot 0 parked
    for i in range(steps):
        tok[1], pos[1] = tokens[n + i], n + i
        logits, kp, vp, _ = decode(tok, pos, kp, vp, tables)
        got.append(np.asarray(logits[1]))
    return np.stack(got), (kp, vp)


@pytest.fixture(scope="module")
def model():
    params = build(CFG)
    tokens = np.asarray(jax.random.randint(
        jax.random.PRNGKey(2), (43 + STEPS,), 0, 97), np.int32)
    want, keep = jax.jit(lambda t: reference.forward(
        params, t, PUBLISHED, with_selection=True))(tokens)
    return params, tokens, np.asarray(want), np.asarray(keep)


def rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def test_chunks_then_token_steps_are_the_references_full_forward(model):
    """``index_topk`` 8 against 47 positions: every query past the eighth
    selects, in the chunks (a mask) and in the steps (a gather)."""
    params, tokens, want, keep = model
    assert (keep.sum(-1)[:, 8:] == 8).all() and keep.shape == (2, 47, 47)
    got, (kp, vp) = served(CFG, params, tokens, 43, (16, 16, 11))
    assert rel(got, want[42:]) < 1e-5
    assert float(np.abs(want).max()) > 0.1
    # two pools a position, addressed by one table
    assert kp.shape == (2, 11, PAGE, 128) and vp.shape == (2, 11, PAGE, 16)
    assert float(jnp.abs(vp[:, 2:8]).min()) > 0      # keys were written
    assert float(jnp.abs(vp[:, 1]).max()) == 0       # and not to another's


def test_one_chunk_is_three_chunks(model):
    params, tokens, want, _ = model
    one, _ = served(CFG, params, tokens, 43, (43,), steps=1)
    three, _ = served(CFG, params, tokens, 43, (16, 16, 11), steps=1)
    assert rel(one, three) < 1e-5
    assert rel(one, want[42:44]) < 1e-5


def test_a_selection_as_long_as_the_sequence_is_the_layer_without_one(model):
    """With ``index_topk`` at or over the sequence's length nothing is
    deselected: the indexer model gives what the same weights give as plain
    latent attention (``_mla_expanded`` in the prefill, the absorbed read of
    every live page in the steps)."""
    params, tokens, _, _ = model
    wide = dataclasses.replace(CFG, index_topk=SEQ)
    plain = dataclasses.replace(CFG, index_heads=0, index_head_dim=0,
                                index_topk=0)
    without = jax.tree_util.tree_map_with_path(
        lambda path, a: a, {**params, **{group: {
            **params[group], "attn": {
                k: v for k, v in params[group]["attn"].items()
                if not k.startswith("index_")}}
            for group in ("dense_layers", "layers")}})
    got, _ = served(wide, params, tokens, 43, (16, 16, 11))
    kp, vp = llama.llama_init_paged_cache(plain, SEQ // PAGE + 3, PAGE)
    assert vp is None
    table = np.arange(2, SEQ // PAGE + 2, dtype=np.int32)[None]
    padded = np.zeros((1, 48), np.int32)
    padded[0, :43] = tokens[:43]
    prefill, decode = (functools.partial(f, without)
                       for f in programs(plain))
    logits, kp, vp, _ = prefill(padded, np.int32(43), kp, vp, table)
    want = [np.asarray(logits[0])]
    tables = np.zeros((2, SEQ // PAGE), np.int32)
    tables[1] = table[0]
    tok, pos = np.zeros((2,), np.int32), np.zeros((2,), np.int32)
    for i in range(STEPS):
        tok[1], pos[1] = tokens[43 + i], 43 + i
        logits, kp, vp, _ = decode(tok, pos, kp, vp, tables)
        want.append(np.asarray(logits[1]))
    assert rel(got, np.stack(want)) < 1e-5
    # ... and the selection of 8 is another model
    narrow, _ = served(CFG, params, tokens, 43, (16, 16, 11))
    assert rel(narrow, np.stack(want)) > 1e-2


def test_plain_latent_pages_take_a_start_too(model):
    """Chunked prefill is written for latent pages with or without an
    indexer: a plain latent model's chunks over its pages give its one
    call's logits."""
    params, tokens, _, _ = model
    plain = dataclasses.replace(CFG, index_heads=0, index_head_dim=0,
                                index_topk=0)
    assert llama.llama_prefill_chunks(plain)
    kp, vp = llama.llama_init_paged_cache(plain, SEQ // PAGE + 3, PAGE)
    table = np.arange(2, SEQ // PAGE + 2, dtype=np.int32)[None]
    prefill = functools.partial(programs(plain)[0], params)
    padded = np.zeros((1, 48), np.int32)
    padded[0, :43] = tokens[:43]
    whole = prefill(padded, np.int32(43), kp, vp, table)[0]
    for start, width in ((0, 24), (24, 19)):
        chunk = np.zeros((1, 24), np.int32)
        chunk[0, :width] = tokens[start:start + width]
        logits, kp, vp, _ = prefill(chunk, np.int32(width), kp, vp, table,
                                    np.int32(0), np.int32(start))
    assert rel(np.asarray(logits), np.asarray(whole)) < 1e-5


@pytest.mark.parametrize("change, says", [
    ({"kv_lora_rank": 0, "q_lora_rank": 0, "qk_nope_dim": 0,
      "qk_rope_dim": 0, "v_head_dim": 0, "rope_yarn": None},
     "not latent"),
    ({"q_lora_rank": 0}, "query bottleneck"),
    ({"hc_mult": 2}, "hc_mult"),
    ({"ut_steps": 2}, "ut_steps > 1"),
    ({"index_topk": 0}, "index_topk"),
    ({"index_heads": 0}, "belong to index_heads"),
    ({"expert_groups": (4, 5)}, "expert_groups"),
    ({"expert_groups": (3, 2)}, "expert_groups"),
    ({"expert_groups": (4, 2), "router_scoring": "softmax",
      "router_bias": False}, "sigmoid"),
    ({"expert_groups": (8, 1)}, "experts_per_token"),
])
def test_check_refuses_what_is_not_written(change, says):
    with pytest.raises(ValueError, match=says):
        llama._check(dataclasses.replace(CFG, **change))


@pytest.mark.parametrize("pattern", [("linear", "full"), ("conv", "full")])
def test_an_indexer_beside_slot_rows_is_refused(pattern):
    more = {"linear_heads": 2, "linear_key_dim": 8, "linear_value_dim": 8} \
        if "linear" in pattern else {}
    with pytest.raises(ValueError, match="layer_pattern"):
        llama._check(dataclasses.replace(CFG, layer_pattern=pattern, **more))


def test_what_cannot_start_past_zero_says_so():
    """K/V pages and slot rows: ``llama_prefill_chunks`` is False and a
    ``start`` raises before anything is traced; the training trunk refuses
    the indexer."""
    plain = LlamaConfig.tiny()
    assert not llama.llama_prefill_chunks(plain)
    params = jax.eval_shape(lambda: llama.llama_init(
        jax.random.PRNGKey(0), plain))
    with pytest.raises(NotImplementedError, match="latent pages"):
        llama.llama_prefill(params, plain, np.zeros((1, 8), np.int32), 8,
                            None, None, np.zeros((1, 4), np.int32), 0, 0)
    with pytest.raises(NotImplementedError, match="indexer"):
        llama.llama_hidden({}, np.zeros((1, 8), np.int32), CFG)
