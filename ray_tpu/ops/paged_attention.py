"""Paged attention for KV-cache decode (reference: vLLM PagedAttention;
JAX analog `jax.experimental.pallas.ops.tpu.paged_attention`).

Serving many concurrent sequences from one replica needs a KV cache that
is neither per-sequence-contiguous (internal fragmentation kills batch
size) nor re-run-the-prefix (quadratic decode).  Instead K/V live in a
pool of fixed-size **pages** shared by all sequences, and each sequence
maps its positions to pages through a small **page table** — exactly
virtual memory for attention.  The layouts:

    q                [B, N, H]              one query token per sequence
    k_pages, v_pages [L, P, page, NKV*H]    every layer's pool, token-major
    layer            scalar int32           the layer a call reads or writes
    lengths          [B] int32              valid positions per sequence
    page_table       [B, maxp] int32        page ids per sequence

``L`` counts pool layers: a model's layers or, for a looped model that runs
its stack ``ut_steps`` times with a cache for every pass, ``ut_steps x
layers``, pass ``t``'s layer ``l`` at ``t * layers + l``
(``models/llama.py``).  The pools of ALL layers are one array each, and the three functions take
the layer's index: the models carry the pools through their layer scan and
a step scatters one token a sequence into the whole pool (``append_kv``; a
prompt's, ``prefill_kv``) and gathers ``(layer, page_table)`` from it.
With the pools donated by the caller, the TPU compiler then updates them in
place; handed to the scan a layer at a time (``xs`` in, ``ys`` out) every
layer's pool was sliced out and copied back once a call.  Token-major with
a token's heads folded into one axis of ``NKV*H`` lanes is the layout the
scatter wants (its window trailing and contiguous) and the one that compiles
without a relayout of the pool for every family: with the heads apart
``[L, P, page, NKV, H]`` a head size under the TPU's 128 lanes (GPT-2's 64)
makes the compiler carry the pool in another layout and copy it whole, a
layer at a time (PERF.md, PR 29).  KV-head-major pools ``[L, NKV, P, page,
H]`` compile to a whole-pool relayout before and after the scatter.  To
shard the KV heads over a model axis, shard the last axis in whole heads.

A second page kind, for latent attention (MLA; ``models/llama.py`` with
``kv_lora_rank``): a position's cache is ONE row of ``W = rank + rope``
values, the normed compressed key-value and the rotated key all heads share,
so there is one pool and no V pool:

    latent_pages     [L, P, page, Wp]    every layer's pool, token-major;
                                         Wp = W rounded up to whole 128-lane
                                         tiles (``latent_width``), the
                                         columns past W zero
    q                [B, N, W]           a head's query with the key
                                         expansion absorbed into it

Why the padding.  An array whose last axis is no multiple of the 128 lanes
(``[L, P, page, 576]``) is given another device layout on the TPU (the page
axis minor) and every program would copy the whole pool in and out of the
layout it computes in (0.9 GB each way a step at Xing4.0's size; compiled for
a described v5e, PERF.md PR 34).  Until PR 50 a page's positions were folded
into the last axis instead (``[L, P, page * W]``, exactly W values a
position), which cured the layout and left a page ONE row of a tiled ``[P,
page * W]`` array: sharing its packed tiles with fifteen other pages, no
contiguous run, a position's values starting at a multiple of 64 lanes, so
nothing could copy a page where it lay and the read had to gather the rung
and lay the gathered rows out again.  At 640 columns for 576 a page of one
layer is ``page x 5`` whole tiles in one run (20 KB), the kind of thing
``ops/paged_read.py`` copies, for 11% of air in a read that no longer pays
for the table's width.  The padding is zeros, written with every row and
never with anything else, and a query is padded with zeros to match, so the
scores are exact.  ``append_latent`` / ``prefill_latent`` scatter rows as
``append_kv`` / ``prefill_kv`` do (a prompt's, whole pages at a time), and
``paged_latent_attention`` scores every head against the one row of a
position and weighs the rows' first ``rank`` columns: it returns attention
over the COMPRESSED values, which the caller expands.  Page 0, the page
table and the layer index mean what they mean above.

A model that generates by diffusion over blocks (``models/llama.py`` with
``block_length``) steps ``B`` positions a sequence: ``append_block_kv``
writes a block's rows and ``paged_block_attention`` reads for its ``B``
query rows at once; the pages are the K/V kind above, no third kind.

This file is the jnp implementation (gather + masked softmax) of every
read, and for the token step's two reads (K/V pages and latent pages) also
the selector: ``paged_attention`` and ``paged_latent_attention`` run as
``ops/paged_read.py``'s Pallas kernel where ``paged_read_kind`` says so, from
what it can observe: the backend is not the CPU, a head (a latent query: the
padded row) is a whole number of 128-lane tiles or, with several query heads
a K/V head in rows of whole tiles, half a tile (LFM2's 64), a page's rows are whole
sublane tiles of the pools' type (16 for bfloat16: every served
configuration; the engine's default page of 8 in the CPU tests is not), and
the queries are of the pools' type.  The kernel leaves the pools in HBM,
copies for each sequence only the pages its own length reaches, each where
it lies (a latent page ONCE, for both products), and reads a head as a run
of lanes of the copied rows: no gathered ``[B, W x page, NKV*H]`` in HBM, no
relayout of it by heads, no page of the rung that the sequence does not
hold.  The gather is exact: given identical page contents it reproduces
dense attention bit-for-bit in f32, which is what the paged-vs-dense CPU
equivalence tests assert; it is the kernel's reference
(``tests/test_paged_attention_kernel.py``, ``tests/test_paged_read_h64.py``)
and what every other case runs: GPT-2's equal heads of 64, the CPU, and the
one read that the kernel is not written for yet, the block step's.

``paged_block_attention`` (and the token step's reads where the kernel is
not picked) gathers every column of the ``page_table`` it is given, for
every sequence, and masks by ``lengths``: the table's width, not the
lengths, sets its cost.  The caller chooses it: the serving engine hands a
step the first ``W`` columns of its rows, ``W`` the least of a few compiled
widths that holds the batch's longest sequence (``serve/engine/engine.py``,
``decode_rungs``); the page of every ``pos`` a step appends at and of every
position under ``lengths`` has to lie inside the table.  The columns left
out held positions whose probability is exactly 0, so a narrower table gives
a wider one's result up to the order of a shorter sum.  Under the kernel the
width bounds only the scalars it prefetches: each sequence reads as far as
its own length.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.ops import paged_read
# bound here by this name: a test replaces it on this module alone
from ray_tpu.ops.kernel_source import kernels_compiled as _kernel_backend


def paged_read_kind(q, k_pages) -> str:
    """What ``paged_attention`` (``paged_latent_attention``: ``q`` padded to
    the pool's row, ``k_pages`` the one pool) reads these pools for these
    queries with, "kernel" or "gather", from what it can observe of them
    (arrays or their shapes): the backend, and whether the kernel is written
    for the operands (``paged_read.supported``: heads of whole 128-lane
    tiles, or grouped heads of half a tile in rows of whole tiles, pages of
    whole sublane tiles, one type).  Measured on the chip at
    the five served K/V shapes and at Xing's latent pages
    (``scripts/paged_read_sweep.py``; PERF.md section 6, PR 49 and PR 50)
    and at LFM2's 32 / 8 heads of 64 in its cell's traced run (PR 55); a
    shape that reads slower through the kernel is named here."""
    if _kernel_backend() and paged_read.supported(
            q.shape, q.dtype, k_pages.shape, k_pages.dtype):
        return "kernel"
    return "gather"


def paged_attention(q: jax.Array, k_pages: jax.Array, v_pages: jax.Array,
                    layer: jax.Array, lengths: jax.Array,
                    page_table: jax.Array, *,
                    sm_scale: Optional[float] = None) -> jax.Array:
    """Single-token decode attention against one layer of the paged K/V.

    ``q`` [B, N, H]; ``k_pages``/``v_pages`` [L, P, page, NKV*H];
    ``layer`` scalar int32; ``lengths`` [B] (positions < length attend, so
    the current token's K/V must already be written at position
    length-1); ``page_table`` [B, maxp].  GQA when N > NKV (N % NKV == 0).
    Returns [B, N, H] in q's dtype; softmax runs in f32.
    """
    B, N, H = q.shape
    page, NKV = k_pages.shape[2], k_pages.shape[3] // H
    if N % NKV:
        raise ValueError(f"query heads {N} not a multiple of KV heads {NKV}")
    rep = N // NKV
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(H)
    if paged_read_kind(q, k_pages) == "kernel":
        with jax.named_scope("paged_read"):
            return paged_read.paged_read_attention(
                q, k_pages, v_pages, layer, lengths, page_table,
                sm_scale=scale)
    maxp = page_table.shape[1]
    S = maxp * page

    # Gather each sequence's pages of this layer, straight from the whole
    # pool: [B, maxp, page, NKV*H] -> [B, S, NKV, H]
    with jax.named_scope("paged_read"):
        k = k_pages[layer, page_table].reshape(B, S, NKV, H)
        v = v_pages[layer, page_table].reshape(B, S, NKV, H)

    qg = q.reshape(B, NKV, rep, H)
    scores = jnp.einsum("bkrh,bskh->bkrs", qg, k) * scale
    valid = jnp.arange(S)[None] < lengths[:, None]          # [B, S]
    scores = jnp.where(valid[:, None, None],
                       scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkrs,bskh->bkrh", probs, v)
    return out.reshape(B, N, H)


def _folded(x: jax.Array, pool: jax.Array) -> jax.Array:
    """Tokens' K or V [T, NKV, H] as the pool stores a token: [T, NKV*H]."""
    return x.reshape(x.shape[0], -1).astype(pool.dtype)


def append_kv(k_pages: jax.Array, v_pages: jax.Array, layer: jax.Array,
              k_new: jax.Array, v_new: jax.Array, pos: jax.Array,
              page_table: jax.Array):
    """Scatter one token's K/V per sequence into one layer of the pools.

    ``k_new``/``v_new`` [B, NKV, H]; ``layer`` scalar int32; ``pos`` [B]
    target positions; ``page_table`` [B, maxp].  Sequences route through
    their own pages so the scatter never conflicts; callers park inactive
    batch slots on page 0 (the scratch sink the allocator reserves) by
    handing them an all-zero page-table row and pos 0.
    """
    page = k_pages.shape[2]
    with jax.named_scope("paged_append"):
        pid = jnp.take_along_axis(page_table, (pos // page)[:, None],
                                  axis=1)[:, 0]                  # [B]
        slot = pos % page
        return (k_pages.at[layer, pid, slot].set(_folded(k_new, k_pages)),
                v_pages.at[layer, pid, slot].set(_folded(v_new, v_pages)))


def append_block_kv(k_pages: jax.Array, v_pages: jax.Array,
                    layer: jax.Array, k_new: jax.Array, v_new: jax.Array,
                    pos0: jax.Array, page_table: jax.Array):
    """Scatter a block of ``B`` positions' K/V per sequence into one layer
    of the pools: ``k_new``/``v_new`` [S, NKV, B, H] (head-major, as the
    rotation leaves them) go to positions ``pos0 .. pos0 + B - 1``; ``pos0``
    [S] is a multiple of ``B`` and the page size a multiple of ``B``, so a
    block lies in ONE page; ``page_table`` [S, maxp].  Inactive slots park
    on page 0 (an all-zero row and ``pos0`` 0), as in ``append_kv``."""
    page = k_pages.shape[2]
    S, _, B, _ = k_new.shape
    with jax.named_scope("paged_append"):
        pid = jnp.take_along_axis(page_table, (pos0 // page)[:, None],
                                  axis=1)                        # [S, 1]
        slot = (pos0 % page)[:, None] + jnp.arange(B)            # [S, B]

        def rows(x, pool):           # [S, NKV, B, H] -> [S, B, NKV*H]
            return jnp.swapaxes(x, 1, 2).reshape(S, B, -1).astype(pool.dtype)
        return (k_pages.at[layer, pid, slot].set(rows(k_new, k_pages)),
                v_pages.at[layer, pid, slot].set(rows(v_new, v_pages)))


def paged_block_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, layer: jax.Array,
                          lengths: jax.Array, page_table: jax.Array
                          ) -> jax.Array:
    """``paged_attention`` for ``B`` query rows a sequence that all see the
    same positions: ``q`` [S, N, B, H]; every row attends to the positions
    under ``lengths`` [S] (a block's rows see the whole block, both ways,
    and everything before it: no mask inside the block, so ``lengths`` is
    the block's end and its K/V must already be written).  Returns [S, N,
    B, H] in q's dtype; softmax in f32."""
    S, N, B, H = q.shape
    page, NKV = k_pages.shape[2], k_pages.shape[3] // H
    if N % NKV:
        raise ValueError(f"query heads {N} not a multiple of KV heads {NKV}")
    T = page_table.shape[1] * page
    with jax.named_scope("paged_read"):
        k = k_pages[layer, page_table].reshape(S, T, NKV, H)
        v = v_pages[layer, page_table].reshape(S, T, NKV, H)
    qg = q.reshape(S, NKV, N // NKV, B, H)
    scores = jnp.einsum("skrbh,stkh->skrbt", qg, k) / np.sqrt(H)
    valid = jnp.arange(T)[None] < lengths[:, None]              # [S, T]
    scores = jnp.where(valid[:, None, None, None],
                       scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    out = jnp.einsum("skrbt,stkh->skrbh", probs, v)
    return out.reshape(S, N, B, H)


def prefill_kv(k_pages: jax.Array, v_pages: jax.Array, layer: jax.Array,
               k_seq: jax.Array, v_seq: jax.Array, length: jax.Array,
               page_table_row):
    """Scatter a whole (padded) prompt's K/V for ONE sequence into one
    layer of the pools.

    ``k_seq``/``v_seq`` [NKV, S, H] (head-major, as the dense attention
    beside it takes them) with S a multiple of the page size; ``layer``
    scalar int32; ``length`` scalar int32 true length; ``page_table_row``
    [maxp].  Positions >= length (padding) are routed to scratch page 0 so
    the sequence only dirties the pages it reserved.
    """
    page = k_pages.shape[2]
    S = k_seq.shape[1]
    with jax.named_scope("paged_append"):
        pos = jnp.arange(S)
        pid = jnp.where(pos < length, page_table_row[pos // page], 0)
        slot = pos % page
        k_seq = _folded(jnp.swapaxes(k_seq, 0, 1), k_pages)
        v_seq = _folded(jnp.swapaxes(v_seq, 0, 1), v_pages)
        return (k_pages.at[layer, pid, slot].set(k_seq),
                v_pages.at[layer, pid, slot].set(v_seq))


def latent_width(rank: int, rope: int) -> int:
    """Columns of a latent page's row: ``rank + rope`` rounded up to whole
    128-lane tiles (576 -> 640), the columns past ``rank + rope`` zero."""
    return -(-(rank + rope) // paged_read._LANES) * paged_read._LANES


def _padded(x: jax.Array, pool: jax.Array) -> jax.Array:
    """Latent rows [.., W] as the pool stores them: [.., Wp], zeros past
    W, in the pool's type."""
    widths = [(0, 0)] * (x.ndim - 1) + [(0, pool.shape[3] - x.shape[-1])]
    return jnp.pad(x.astype(pool.dtype), widths)


def append_latent(pages: jax.Array, layer: jax.Array, new: jax.Array,
                  pos: jax.Array, page_table: jax.Array) -> jax.Array:
    """Scatter one position's latent row per sequence into one layer of the
    pool: ``new`` [B, W]; ``pos`` [B]; ``page_table`` [B, maxp]; ``pages``
    [L, P, page, Wp], the row of position ``pos`` at ``pos % page`` of its
    page, padded with zeros to ``Wp``.  Inactive slots park on page 0, as in
    ``append_kv``."""
    page = pages.shape[2]
    with jax.named_scope("latent_append"):
        pid = jnp.take_along_axis(page_table, (pos // page)[:, None],
                                  axis=1)[:, 0]                  # [B]
        return pages.at[layer, pid, pos % page].set(_padded(new, pages))


def prefill_latent(pages: jax.Array, layer: jax.Array, seq: jax.Array,
                   length: jax.Array, page_table_row,
                   start: Optional[jax.Array] = None) -> jax.Array:
    """Scatter a whole (padded) prompt's latent rows ``seq`` [S, W] of ONE
    sequence into one layer of the pool ``[L, P, page, Wp]``, a page at a
    time (S is a multiple of the page size).  Pages wholly past ``length``
    go to scratch page 0; the padding that shares the prompt's last page
    lands in the slots the sequence's next positions will overwrite before
    any step reads them.  With ``start`` (a multiple of the page size) the
    rows are a CHUNK of the prompt, positions ``start .. start + S - 1``,
    ``length`` of them real: they go to the pages that hold those positions.
    Any pool of rows a position is written so (the indexer's keys, ``[L, P,
    page, d]``)."""
    S = seq.shape[0]
    page, Wp = pages.shape[2:]
    with jax.named_scope("latent_append"):
        first = jnp.arange(S // page) * page        # a page's first position
        pid = jnp.where(
            first < length,
            page_table_row[:S // page] if start is None else
            jax.lax.dynamic_slice_in_dim(page_table_row, start // page,
                                         S // page), 0)
        return pages.at[layer, pid].set(
            _padded(seq, pages).reshape(S // page, page, Wp))


def paged_latent_attention(q: jax.Array, pages: jax.Array, layer: jax.Array,
                           lengths: jax.Array, page_table: jax.Array, *,
                           sm_scale: float, rank: int) -> jax.Array:
    """Single-token decode attention against one layer of the latent pool,
    read as it lies.  ``q`` [B, N, W] (W = rank + rope: the absorbed query
    beside the rotated one); ``pages`` [L, P, page, Wp]; positions < length
    attend.  Every head scores against the same row of a position (the
    query padded with zeros to ``Wp``: the scores are exact) and the
    probabilities weigh the rows.  Returns [B, N, rank] in q's dtype: the
    attention over the compressed values, the rows' first ``rank`` columns,
    which the caller expands.  Softmax runs in f32.

    Where ``paged_read_kind`` says so of the padded queries and the pool it
    is ``ops/paged_read.py``'s walk with ONE copy a page, from which both
    products are taken; elsewhere (the CPU, a page that is no whole sublane
    tiles) the gather below, the walk's reference."""
    B, N, W = q.shape
    page, Wp = pages.shape[2:]
    with jax.named_scope("latent_read"):
        q = jnp.pad(q, ((0, 0), (0, 0), (0, Wp - W)))
        if paged_read_kind(q, pages) == "kernel":
            return paged_read.paged_read_attention(
                q, pages, None, layer, lengths, page_table,
                sm_scale=sm_scale, columns=rank)[..., :rank]
        S = page_table.shape[1] * page
        rows = pages[layer, page_table].reshape(B, S, Wp)
        scores = jnp.einsum("bnw,bsw->bns", q, rows) * sm_scale
        valid = jnp.arange(S)[None] < lengths[:, None]           # [B, S]
        scores = jnp.where(valid[:, None], scores.astype(jnp.float32),
                           -1e30)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bns,bsw->bnw", probs, rows)[..., :rank]


# ---------------------------------------------------------------------------
# Learned sparse attention over latent pages (DeepSeek-V3.2's lightning
# indexer; ``models/llama.py`` with ``index_heads``).  A position keeps a
# second row, the indexer's key, in a pool of its own ``[L, P, page, d]``
# beside the latent pages (``append_latent`` / ``prefill_latent`` write it: a
# row a position is a row a position).  A query scores every position it may
# see, ``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . k[s])``, keeps the ``k``
# positions that score highest (EXACTLY those: a tie goes to the lower
# position, as ``lax.top_k`` breaks it) and its attention runs over them
# alone.  The token step gathers a sequence's keys through its page table,
# takes the top ``k`` and gathers those latent rows (``paged_index_scores``,
# ``select_positions``, ``paged_latent_attention_selected``: plain jnp; the
# kernel that would walk the key pages and copy the selected rows where they
# lie is not written).  A prompt's chunk scores its queries against the whole
# table's positions in blocks and turns the selection into a mask a QUERY,
# shared by all heads (``index_scores``, ``select_mask``), for the chunk's
# attention over the pages, which IS a kernel on the chip
# (``ops/latent_prefill.py``: it reads the mask a tile a grid step, exactly).
# The scores are float32 sums of exact products of the stored (bfloat16)
# queries and keys, in both.


def block_size(n: int, want: int, of: int = 1) -> int:
    """The largest divisor of ``n`` that is at most ``want`` and a multiple
    of ``of``; 0 if there is none."""
    return max((b for b in range(of, min(n, want) + 1, of) if n % b == 0),
               default=0)


def paged_rows(pages: jax.Array, layer: jax.Array, page_table_row,
               first: jax.Array, count: int) -> jax.Array:
    """``count`` pages of one sequence, from its ``first``-th on, as rows
    ``[count * page, W]`` in the order of their positions."""
    pids = jax.lax.dynamic_slice_in_dim(page_table_row, first, count)
    return pages[layer, pids].reshape(count * pages.shape[2], pages.shape[3])


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array,
                 q_block: int = 512, k_block: int = 1024) -> jax.Array:
    """``I[t, s] = sum_j w[t, j] ReLU(q[t, j] . keys[s])`` in float32: q [T,
    H, d], w [T, H] float32, keys [S, d] -> [T, S].  Computed a block of
    queries by a block of keys at a time, so that the heads' products
    ``[H, T, S]`` never exist whole (18 GB at 64 heads, 4,096 queries and
    17,408 keys)."""
    T, H, d = q.shape
    S = keys.shape[0]
    tb, sb = block_size(T, q_block), block_size(S, k_block)

    def rows(block):
        qb, wb = block                              # [tb, H, d], [tb, H]

        def cols(kb):                               # [sb, d]
            dots = jnp.einsum("thd,sd->ths", qb, kb,
                              preferred_element_type=jnp.float32)
            return jnp.sum(jax.nn.relu(dots) * wb[:, :, None], axis=1)
        out = jax.lax.map(cols, keys.reshape(S // sb, sb, d))
        return jnp.swapaxes(out, 0, 1).reshape(tb, S)
    return jax.lax.map(rows, (q.reshape(T // tb, tb, H, d),
                              w.reshape(T // tb, tb, H))).reshape(T, S)


def _sortable(x: jax.Array) -> jax.Array:
    """float32 -> uint32 that orders as the floats do."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >> 31 == 1, ~bits, bits | jnp.uint32(1 << 31))


def select_mask(scores: jax.Array, valid: jax.Array, k: int) -> jax.Array:
    """For every row of ``scores`` [T, S] float32 the mask of its ``k``
    largest among the positions ``valid`` [T, S] marks (all of them where
    they are ``k`` or fewer), a tie to the lower position: what
    ``lax.top_k`` of the masked row keeps, as a mask and without a sort.
    The ``k``-th largest value of a row is found bit by bit (32 counts of
    the row against a threshold), exact in any case; the rows' ties at that
    value, which float32 sums of 64 products all but never have, are
    resolved by position only where some row has one to resolve."""
    T, S = scores.shape
    if k >= S:
        return valid
    # an invalid position orders below every score (-inf is 0x007FFFFF)
    key = jnp.where(valid, _sortable(scores), jnp.uint32(0))

    def bit(i, kth):
        cand = kth | (jnp.uint32(1) << (31 - i).astype(jnp.uint32))
        enough = jnp.sum(key >= cand[:, None], axis=1) >= k
        return jnp.where(enough, cand, kth)
    kth = jax.lax.fori_loop(0, 32, bit, jnp.zeros((T,), jnp.uint32))
    above = key > kth[:, None]
    ties = key == kth[:, None]
    room = k - jnp.sum(above, axis=1)               # of the ties, how many
    crowded = jnp.any(jnp.sum(ties & valid, axis=1) > room)
    keep = jax.lax.cond(
        crowded,
        lambda: above | (ties & (jnp.cumsum(ties, axis=1) <= room[:, None])),
        lambda: above | ties)
    return keep & valid


def paged_index_scores(q: jax.Array, w: jax.Array, key_pages: jax.Array,
                       layer: jax.Array, page_table: jax.Array) -> jax.Array:
    """The token step's index scores: q [B, H, d], w [B, H] float32, the
    indexer's key pool ``[L, P, page, d]`` -> [B, S] float32 over every
    position of the ``page_table`` [B, maxp] it is given (S = maxp x page);
    the caller masks by length."""
    B = q.shape[0]
    keys = key_pages[layer, page_table].reshape(B, -1, key_pages.shape[3])
    dots = jnp.einsum("bhd,bsd->bhs", q, keys,
                      preferred_element_type=jnp.float32)
    return jnp.sum(jax.nn.relu(dots) * w[:, :, None], axis=1)


def select_positions(scores: jax.Array, lengths: jax.Array, k: int):
    """The ``min(k, length)`` positions under ``lengths`` [B] that score
    highest in ``scores`` [B, S]: (positions [B, k'] int32, which of them
    count [B, k'] bool), ``k'`` = ``min(k, S)``; exact (``lax.top_k``; a
    tie to the lower position)."""
    S = scores.shape[1]
    live = jnp.arange(S)[None] < lengths[:, None]
    _, at = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), min(k, S))
    return at.astype(jnp.int32), \
        jnp.arange(at.shape[1])[None] < lengths[:, None]


def paged_latent_attention_selected(
        q: jax.Array, pages: jax.Array, layer: jax.Array, at: jax.Array,
        counted: jax.Array, page_table: jax.Array, *, sm_scale: float,
        rank: int) -> jax.Array:
    """``paged_latent_attention`` over the positions ``at`` [B, K] alone
    (those that ``counted`` [B, K] marks): the rows are gathered through the
    page table, a row a selected position, and nothing else of the pool is
    read.  q [B, N, W] -> [B, N, rank]."""
    B, N, W = q.shape
    page, Wp = pages.shape[2:]
    pid = jnp.take_along_axis(page_table, at // page, axis=1)    # [B, K]
    rows = pages[layer, pid, at % page]                          # [B, K, Wp]
    q = jnp.pad(q, ((0, 0), (0, 0), (0, Wp - W)))
    scores = jnp.einsum("bnw,bkw->bnk", q, rows) * sm_scale
    scores = jnp.where(counted[:, None], scores.astype(jnp.float32), -1e30)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bnk,bkw->bnw", probs, rows)[..., :rank]
