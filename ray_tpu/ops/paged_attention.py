"""Paged attention for KV-cache decode (reference: vLLM PagedAttention;
JAX analog `jax.experimental.pallas.ops.tpu.paged_attention`).

Serving many concurrent sequences from one replica needs a KV cache that
is neither per-sequence-contiguous (internal fragmentation kills batch
size) nor re-run-the-prefix (quadratic decode).  Instead K/V live in a
pool of fixed-size **pages** shared by all sequences, and each sequence
maps its positions to pages through a small **page table** — exactly
virtual memory for attention.  The layouts follow the TPU reference op:

    q                [B, N, H]           one query token per sequence
    k_pages, v_pages [NKV, P, page, H]   KV-head-major page pools
    lengths          [B] int32           valid positions per sequence
    page_table       [B, maxp] int32     page ids per sequence

KV-head-major pages make the GQA sharding trivial: shard dim 0 of the
pools and the head dim of q over the model axis, and every chip decodes
its head slice of ALL sequences with no cross-chip traffic.

This file is the jnp reference implementation (gather + masked softmax
— the decode working set is one token per sequence, so XLA's fused
gather is adequate on CPU and fine on TPU at small batch; a Pallas
HBM-resident kernel like flash_attention.py's is the upgrade path when
pools outgrow VMEM).  It is exact: given identical page contents it
reproduces dense attention bit-for-bit in f32, which is what the
paged-vs-dense CPU equivalence tests assert.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    lengths: jax.Array, page_table: jax.Array, *,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """Single-token decode attention against paged K/V.

    ``q`` [B, N, H]; ``k_pages``/``v_pages`` [NKV, P, page, H];
    ``lengths`` [B] (positions < length attend, so the current token's
    K/V must already be written at position length-1); ``page_table``
    [B, maxp].  GQA when N > NKV (N % NKV == 0).  Returns [B, N, H] in
    q's dtype; softmax runs in f32.
    """
    B, N, H = q.shape
    NKV, _P, page, _H = k_pages.shape
    if N % NKV:
        raise ValueError(f"query heads {N} not a multiple of KV heads {NKV}")
    rep = N // NKV
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(H)
    maxp = page_table.shape[1]
    S = maxp * page

    # Gather each sequence's pages: [NKV, B, maxp, page, H] -> [NKV, B, S, H]
    with jax.named_scope("paged_read"):
        k = k_pages[:, page_table].reshape(NKV, B, S, H)
        v = v_pages[:, page_table].reshape(NKV, B, S, H)

    qg = q.reshape(B, NKV, rep, H)
    scores = jnp.einsum("bkrh,kbsh->bkrs", qg, k) * scale
    valid = jnp.arange(S)[None] < lengths[:, None]          # [B, S]
    scores = jnp.where(valid[:, None, None],
                       scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrs,kbsh->bkrh", probs, v)
    return out.reshape(B, N, H)


def append_kv(k_pages: jax.Array, v_pages: jax.Array, k_new: jax.Array,
              v_new: jax.Array, pos: jax.Array, page_table: jax.Array):
    """Scatter one token's K/V per sequence into the pools.

    ``k_new``/``v_new`` [B, NKV, H]; ``pos`` [B] target positions;
    ``page_table`` [B, maxp].  Sequences route through their own pages so
    the scatter never conflicts; callers park inactive batch slots on
    page 0 (the scratch sink the allocator reserves) by handing them an
    all-zero page-table row and pos 0.
    """
    page = k_pages.shape[2]
    with jax.named_scope("paged_append"):
        pid = jnp.take_along_axis(page_table, (pos // page)[:, None],
                                  axis=1)[:, 0]                  # [B]
        slot = pos % page
        k_new = jnp.swapaxes(k_new, 0, 1).astype(k_pages.dtype)  # [NKV,B,H]
        v_new = jnp.swapaxes(v_new, 0, 1).astype(v_pages.dtype)
        return (k_pages.at[:, pid, slot].set(k_new),
                v_pages.at[:, pid, slot].set(v_new))


def prefill_kv(k_pages: jax.Array, v_pages: jax.Array, k_seq: jax.Array,
               v_seq: jax.Array, length: jax.Array, page_table_row):
    """Scatter a whole (padded) prompt's K/V for ONE sequence.

    ``k_seq``/``v_seq`` [NKV, S, H] with S a multiple of the page size;
    ``length`` scalar int32 true length; ``page_table_row`` [maxp].
    Positions >= length (padding) are routed to scratch page 0 so the
    sequence only dirties the pages it reserved.
    """
    page = k_pages.shape[2]
    S = k_seq.shape[1]
    with jax.named_scope("paged_append"):
        pos = jnp.arange(S)
        pid = jnp.where(pos < length, page_table_row[pos // page], 0)
        slot = pos % page
        return (k_pages.at[:, pid, slot].set(k_seq.astype(k_pages.dtype)),
                v_pages.at[:, pid, slot].set(v_seq.astype(v_pages.dtype)))
