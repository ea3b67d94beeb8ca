"""A prompt chunk's latent attention over the sequence's pages
(``ops/latent_prefill.py``, ISSUE 58): the kernel in Pallas interpret mode on
the CPU against ``models/llama.py::_mla_expanded``'s mathematics over the
WHOLE sequence with the mask explicit, at small widths (values wider than
keys, so nothing can lean on equal widths) and with blocks small enough that
a chunk walks several, and what decides whether the kernel is compiled.

Tolerance.  In float32 the walk differs from the whole-sequence softmax by
the order of a float32 sum (block by block under a running maximum): 1e-5 on
results of order 1.  In bfloat16 the walk rounds the probabilities before
their normalisation and the result once, the reference after it: 2**-6.
"""

import functools
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.llama import LlamaConfig
from ray_tpu.ops import latent_prefill

S, T, QB, KB = 32, 96, 16, 32
CFG = LlamaConfig(
    vocab_size=97, max_seq_len=T, num_layers=1, num_heads=4, num_kv_heads=4,
    embed_dim=32, mlp_dim=16, dtype=jnp.float32, kv_lora_rank=24,
    q_lora_rank=0, qk_nope_dim=16, qk_rope_dim=8, v_head_dim=32)
TOLERANCE = {jnp.float32: 1e-5, jnp.bfloat16: 2.0 ** -6}


def expanded(cfg, p, q_nope, q_rope, latent, mask):
    """``_mla_expanded`` of one sequence with ``mask`` [S, S] explicit."""
    rank, dn, dt = cfg.kv_lora_rank, cfg.qk_nope_dim, cfg.dtype
    kv = jnp.einsum("sc,cnh->snh", latent[:, :rank],
                    p["attn"]["wkv_b"].astype(dt))
    scores = (jnp.einsum("qnh,knh->nqk", q_nope, kv[..., :dn])
              + jnp.einsum("qnh,kh->nqk", q_rope, latent[:, rank:])) \
        * llama.mla_softmax_scale(cfg)
    scores = jnp.where(mask[None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(dt)
    return jnp.einsum("nqk,knh->qnh", probs, kv[..., dn:])


def selection(start, seed):
    """A mask a query over the table, inside the causal bound: the chunk's
    first quarter of queries keep ALL of every block they may see, the second
    keep nothing of the table's first block of keys, the third nothing of any
    block but the last they see, the rest a random third; every query keeps
    itself."""
    rng = np.random.default_rng(seed)
    pos = start + np.arange(S)[:, None]
    at = np.arange(T)[None]
    causal = at <= pos
    keep = causal & (rng.random((S, T)) < 0.33)
    keep[:S // 4] = causal[:S // 4]
    keep[S // 4:S // 2, :KB] = False
    third = slice(S // 2, 3 * S // 4)
    keep[third] = causal[third] & (at >= pos[third] // KB * KB)
    return keep | (at == pos)


def operands(dtype, seed):
    """A sequence of T positions: every position's queries, its latent rows
    (as the pages hold them), and the layer's ``wkv_b``."""
    cfg = dataclasses.replace(CFG, dtype=dtype)
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    N, dn, dr = cfg.num_heads, cfg.qk_nope_dim, cfg.qk_rope_dim
    q_nope = jax.random.normal(ks[0], (T, N, dn), dtype)
    q_rope = jax.random.normal(ks[1], (T, N, dr), dtype)
    latent = jax.random.normal(ks[2], (T, cfg.kv_lora_rank + dr), dtype)
    wkv_b = jax.random.normal(
        ks[3], (cfg.kv_lora_rank, N, dn + cfg.v_head_dim), dtype) * 0.3
    return cfg, {"attn": {"wkv_b": wkv_b}}, q_nope, q_rope, latent


CHUNKS = [(0, S, False), (48, 20, False), (0, 20, True), (64, S, True),
          (40, 9, True)]


@pytest.fixture(autouse=True)
def small_blocks(monkeypatch):
    """Blocks of 16 queries and 32 keys, two heads a step: the module's own
    divide nothing this small, and a chunk is to walk several."""
    for name, size in (("_BLOCK_Q", QB), ("_BLOCK_K", KB), ("_HEADS", 2)):
        monkeypatch.setattr(latent_prefill, name, size)


@functools.lru_cache(maxsize=None)
def compiled(dtype, selects):
    """The entry, jitted once a type and kind of mask: ``start`` and
    ``length`` are the program's arguments, as they are the engine's."""
    return jax.jit(functools.partial(
        latent_prefill.latent_chunk_attention,
        sm_scale=llama.mla_softmax_scale(dataclasses.replace(CFG,
                                                             dtype=dtype))))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=lambda v: v.__name__)
@pytest.mark.parametrize("start,length,selects", CHUNKS)
def test_a_chunk_reads_what_the_whole_sequence_reads(start, length, selects,
                                                     dtype):
    """``start`` 0 and past it (40: inside a block of keys), a padded tail
    (``length`` < S, and 9: a whole block of queries is padding), the causal
    bound alone and a selection in which some queries keep nothing of a block
    of keys and some all of it."""
    cfg, p, q_nope, q_rope, latent = operands(dtype, seed=start + length)
    rank, dn = cfg.kv_lora_rank, cfg.qk_nope_dim
    keep = selection(start, seed=1) if selects else None
    mask = np.tril(np.ones((T, T), bool))
    if selects:
        mask[start:start + S] = keep
    want = expanded(CFG, jax.tree.map(f32, p), f32(q_nope),
                    f32(q_rope), f32(latent), mask)[start:start + length]
    if not selects and dtype == jnp.float32:   # the model's own function
        np.testing.assert_allclose(
            want, llama._mla_expanded(cfg, p, q_nope[None], q_rope[None],
                                      latent[None])[0, start:start + length],
            rtol=1e-6, atol=1e-6)
    # what ``_mla_chunk`` hands over: the table's rows expanded once
    wkv_b = p["attn"]["wkv_b"]
    k, v = (jnp.dot(latent[:, :rank], w.reshape(rank, -1))
            for w in (wkv_b[..., :dn], wkv_b[..., dn:]))
    got = compiled(dtype, selects)(
        q_nope[start:start + S], q_rope[start:start + S], k, v,
        latent[:, rank:], None if keep is None else jnp.asarray(keep),
        jnp.int32(start), jnp.int32(length))
    assert got.shape == (S, cfg.num_heads, cfg.v_head_dim)
    assert got.dtype == dtype
    np.testing.assert_allclose(f32(got[:length]), want, rtol=TOLERANCE[dtype],
                               atol=TOLERANCE[dtype])
    assert np.isfinite(np.asarray(f32(got))).all()
    # a block of nothing but padding reads nothing
    padding = -(-length // QB) * QB
    assert float(jnp.abs(f32(got[padding:])).sum()) == 0.0


def f32(a):
    return a.astype(jnp.float32)


def test_what_decides_is_the_backend_and_whole_lane_tiles(monkeypatch):
    """A chunk's attention is the kernel everywhere, "latent_chunk" to the
    engine, and a model's call without a ``start`` is not a chunk's unless it
    has an indexer; the kernel is COMPILED on a chip for heads of whole
    128-lane tiles over a table that blocks of whole tiles divide, and
    interpreted anywhere else."""
    monkeypatch.undo()                                  # the module's blocks
    wide = dataclasses.replace(CFG, qk_nope_dim=128, v_head_dim=128,
                               qk_rope_dim=64)
    said = llama.llama_prefill_attention
    assert [said(c, S, True) for c in (CFG, wide) for S in (128, 4096)] == \
        ["latent_chunk"] * 4
    assert said(wide, 4096) == said(wide, 4096, False) == "dense"
    # an indexer's prefill always runs as a chunk
    index = dataclasses.replace(wide, index_heads=4, index_head_dim=16,
                                index_topk=8, q_lora_rank=24)
    assert said(index, 4096) == "latent_chunk"
    how = latent_prefill._compiled
    assert not how(4096, 17408, 128, 128)               # this backend
    monkeypatch.setattr(latent_prefill, "_kernel_backend", lambda: True)
    assert how(4096, 17408, 128, 128) and how(128, 17408, 128, 256)
    assert not how(4096, 17408 + 16, 128, 128)
    assert not how(4096, 17408, 16, 128) and not how(4096, 17408, 128, 32)
    assert not how(4096 + 8, 17408, 128, 128)
