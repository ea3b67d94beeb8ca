"""The token step's paged read as a Pallas TPU kernel that walks the page
table.

``ops/paged_attention.py::paged_attention`` gathers every column of the
page table for every sequence into ``[B, W x page, NKV*H]`` (written to HBM,
read again), lays the gathered rows out by heads, and takes two products.
This kernel takes the pools as they are stored, ``[L, P, page, NKV*H]``,
LEFT IN HBM, and for each sequence copies only the pages its own
``lengths[b]`` reaches, from ``(layer, page_table[b, j])``, into fast memory
(a page of one layer is one contiguous run: one copy for K, one for V) and
computes the attention there.  ``layer`` (with the table's width),
``lengths`` and the table are prefetched scalars; ``layer`` may be traced (a
model's ``lax.scan`` over its layers).

The grid is the sequences, in order.  A sequence's positions go by blocks
of ``_BLOCK_TOKENS`` positions (several pages), and ``_BUFFERS`` blocks are
in fast memory at a time: one whose products are being taken, the others on
their way.  One chain of copies runs through the whole call: a pointer
(sequence, block) walks the blocks in the order they are computed, that many
blocks ahead, across the sequences' ends, and each block's copies are
started as the block before the one it replaces has been computed.  A block
wholly past ``lengths[b]`` does not exist; the block that holds position
``lengths[b] - 1`` copies the pages as far as that position and masks its
tail (the scores, and the V rows: a product with a probability of 0 is no
cover for what a row past the length may hold).  A parked slot (an all-zero
table row, length 1) costs one page of page 0.  The table's width bounds
nothing: it is handed over flat, at the length of the pool's pages whatever
its width, with the width a scalar beside the layer, so that the programs an
engine compiles for several widths share one trace of this kernel.

A head is a run of ``H`` lanes of a row, and no ``[.., NKV, H]`` view of
anything copied exists.  The products are two a block, not two a head: the
sequence's queries are laid out block-diagonally, ``[N, NKV*H]`` with head
``n``'s query in the columns of its K/V head and zeros elsewhere, so that
``scores [N, T] = queries x block^T`` is one product over the whole row;
``probabilities [N, T] x V block [T, NKV*H]`` gives every head against every
K/V head's columns, of which the diagonal blocks are kept.  At 1-4 query
rows a K/V head the MXU's time is loading the block's tiles, which a product
a head would load as well; the redundant rows ride along.  (PERF.md section
6, PR 49, has what was measured.)

The arithmetic is ``paged_attention``'s: the pools' type into the products,
scores, the running maximum, sum and accumulator in float32 (online softmax
over the blocks), probabilities rounded to the pools' type for the product
with V, the result in ``q``'s type.  Every position ``lengths`` admits is
read.  The result differs from the gather's by the order of a float32 sum,
by where the probabilities are rounded (before the normalisation here, after
it there), and by the scores, which the gather's einsum rounds to the pools'
type and this keeps in float32.

The latent kind (``ops/paged_attention.py::paged_latent_attention``; PR 50)
is the same walk with its page kind read from the operands: handed ONE pool
``[L, P, page, Wp]`` (``v_pages`` None) a page is copied once and both
products are taken from the copy, ``scores = q [N, Wp] x block^T`` and
``probabilities x block[:, :columns]``, the values being the rows' first
``columns`` columns (whole lane tiles: the result comes out cut to them).
There is one K/V head, so no block diagonal; the chain of copies, the online
softmax and the tail's masks are the ones above.  A block is as many pages as
their bytes say (``_resolve``): a latent page is small and the copies' issue,
not their bytes, is what a block of them costs.

A HEAD OF HALF A LANE TILE (H = 64: LFM2's 32 / 8 heads; two K/V heads a
tile, a row of 8 x 64 = 512 columns = 4 tiles) takes the same walk.  What
would cut a tile inside the kernel is done outside it, in jnp, where the
compiler lays it out: the block-diagonal queries ``[B, N, NKV*H]`` are made
before the call (the kernel's ``spread`` is then its operand as it comes) and
the accumulator keeps the whole row ``[N, NKV*H]``, every head's columns
masked to its own K/V head's, so that the result's diagonal blocks are cut
out after the call (a sum over the K/V heads of which one term is not zero:
exact).  Inside, every operand, product and mask is whole lane tiles, as at
128.  The copies, the chain, the online softmax and the tail's masks are the
ones above, and a head of whole tiles traces what it traced before.

On the CPU backend the kernel runs in Pallas interpret mode (the tests).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kernel_source

_LANES = 128
_MASKED = -1e30
# Positions a compute block (16 pages of 16) and blocks in fast memory: 11.8
# MB of K and V at Olmo-Hybrid's 3,840 columns.  On the chip
# (``scripts/paged_read_sweep.py``; PERF.md section 6, PR 49) blocks of 128,
# 256 and 512 positions and 2, 3 and 4 of them read within 4% of each other
# where the bytes bind (85-87% of the live bytes' roofline at the hybrid
# cell's shape); 256 x 3 is the best or within 1% of it at all five shapes.
# Where a page is few bytes (a latent page of 16 rows is 20 KB, a twelfth of
# the hybrid cell's K and V) it is the copies' issue and the loop's own time
# a block that bind, and a block is doubled until its copies are
# ``_BLOCK_BYTES``, the least the K/V shapes were measured at (Mistral's
# 256 positions): 1,024 latent positions read 14-19% faster than 256 (PERF.md
# section 6, PR 50).
_BLOCK_TOKENS = 256
_BLOCK_BYTES = 2 ** 20
_BUFFERS = 3
_VMEM_LIMIT_BYTES = 64 * 2 ** 20

# The kernel's module names no file (kernel_source.py says why).
kernel_source.exclude(__file__)


def sublanes(dtype) -> int:
    """Rows of one packed [8, 128] tile of the type: 8 float32, 16 bf16."""
    return 32 // jnp.dtype(dtype).itemsize


def supported(q_shape, q_dtype, pool_shape, pool_dtype) -> bool:
    """Whether the kernel is written for these operands: heads of whole
    128-lane tiles, or of half a tile (64) in rows of whole tiles with
    several query heads a K/V head (LFM2's 32 / 8; GPT-2's equal heads of 64
    stay with the gather: the block-diagonal row of N = NKV heads is N times
    the work for nothing shared, and no cell has measured it), a page of
    whole sublane tiles of the pool's type (a copy lands on whole tiles of
    the buffer), queries a whole number a K/V head and of the pool's type
    (the products take both as they are)."""
    _, N, H = q_shape
    page, D = pool_shape[2:]
    half = 2 * H == _LANES and D % _LANES == 0 and N > D // H
    return ((H % _LANES == 0 or half)
            and D % H == 0 and N % (D // H) == 0
            and page % sublanes(pool_dtype) == 0
            and jnp.dtype(q_dtype) == jnp.dtype(pool_dtype))


def _resolve(page, page_bytes, pages_per_block, interpret):
    """(pages a compute block, unless the caller says: ``_BLOCK_TOKENS``
    positions, doubled until the copies of a block, ``page_bytes`` a page
    over all pools, are ``_BLOCK_BYTES``; whether to interpret)."""
    if interpret is None:
        interpret = not kernel_source.kernels_compiled()
    if not pages_per_block:
        pages_per_block = max(1, _BLOCK_TOKENS // page)
        while pages_per_block * page_bytes < _BLOCK_BYTES:
            pages_per_block *= 2
    return pages_per_block, interpret


def paged_read_attention(q, k_pages, v_pages, layer, lengths, page_table, *,
                         sm_scale: float, interpret: Optional[bool] = None,
                         pages_per_block: Optional[int] = None,
                         buffers: Optional[int] = None,
                         columns: Optional[int] = None):
    """``paged_attention``'s result by the kernel: ``q`` [B, N, H],
    ``k_pages`` / ``v_pages`` [L, P, page, NKV*H] of q's type, ``layer`` a
    scalar index, ``lengths`` [B] (at least 1 each), ``page_table`` [B,
    maxp] -> [B, N, H] in q's type.

    ``v_pages`` None is the latent kind (``paged_latent_attention``'s
    result): ``k_pages`` [L, P, page, H] is the one pool, a row of it key
    and value both, ``q`` [B, N, H] every head's query against the whole
    row; ``columns`` (whole lane tiles, H if not given) is how many of a
    row's leading columns are values -> [B, N, columns]."""
    if not supported(q.shape, q.dtype, k_pages.shape, k_pages.dtype) or (
            v_pages is None and k_pages.shape[3] != q.shape[2]):
        raise ValueError(
            f"no paged-read kernel for queries {q.dtype}{list(q.shape)} on "
            f"pools {k_pages.dtype}{list(k_pages.shape)}")
    page, D = k_pages.shape[2:]
    ppb, interpret = _resolve(
        page, page * D * k_pages.dtype.itemsize * (1 if v_pages is None
                                                   else 2),
        pages_per_block, interpret)
    # The table goes in flat and at ONE length whatever its width: the
    # pool's pages, the most that sequences can hold between them (a table
    # with more entries than that goes as it is).  Its width goes beside
    # the layer as a scalar.  A serving engine compiles a step for several
    # widths of table (``decode_rungs``); all of them then share one trace
    # of the kernel, which is a few hundred operations of Python a trace
    # (PERF.md section 6, PR 49: ``setup_s``).
    table = page_table.astype(jnp.int32).reshape(-1)
    if table.shape[0] < k_pages.shape[1]:
        table = jnp.pad(table, (0, k_pages.shape[1] - table.shape[0]))
    where = jnp.stack([jnp.asarray(layer, jnp.int32),
                       jnp.int32(page_table.shape[1])])
    if v_pages is not None or not columns or columns % _LANES:
        columns = q.shape[2]
    return _walk(q, k_pages, v_pages, where, lengths.astype(jnp.int32),
                 table, sm_scale=float(sm_scale), ppb=ppb,
                 buffers=buffers or _BUFFERS, interpret=interpret,
                 columns=columns)


# jitted and inlined where it is called, as ``grouped_matmul._tiled`` is: a
# model's programs trace the kernel once a shape, not once a call.
@functools.partial(jax.jit, static_argnames=("sm_scale", "ppb", "buffers",
                                             "interpret", "columns"),
                   inline=True)
def _walk(q, k_pages, v_pages, where, lengths, table, *, sm_scale: float,
          ppb: int, buffers: int, interpret: bool, columns: int):
    B, N, H = q.shape
    page, D = k_pages.shape[2:]
    # the page kind, read from the operands: one pool is latent pages, a
    # copied row key and value both (its first ``columns`` columns)
    latent = v_pages is None
    pools = (k_pages,) if latent else (k_pages, v_pages)
    NKV = D // H
    rep = N // NKV
    T = ppb * page
    # a head of half a lane tile: the queries come block-diagonal, the
    # result goes out a whole row wide (module docstring)
    half = bool(H % _LANES)
    if half:
        own = (jnp.arange(N)[:, None] // rep) == (jnp.arange(D)[None] // H)
        q = jnp.where(own[None], jnp.tile(q, (1, 1, NKV)),
                      jnp.zeros((), q.dtype))
        columns = D
    # whole sublane tiles of query rows; the rows added score against
    # nothing (no K/V head is theirs) and are cut off the result
    Np = -(-N // sublanes(q.dtype)) * sublanes(q.dtype)
    if Np != N:
        q = jnp.pad(q, ((0, 0), (0, Np - N), (0, 0)))

    def kernel(where_ref, lengths_ref, table_ref, q_ref, *refs):
        hbm, (o_ref, *bufs, sems, chain) = refs[:len(pools)], \
            refs[len(pools):]
        kbuf, vbuf = bufs[0], bufs[-1]      # a latent page's one copy is both
        # chain: blocks started, the (sequence, block) to start next, blocks
        # computed; a block's buffer is its number modulo ``buffers``
        STARTED, SEQ, BLOCK, DONE = range(4)
        b = pl.program_id(0)
        layer, W = where_ref[0], where_ref[1]
        length = lengths_ref[b]

        def blocks_of(seq):
            return jnp.maximum(1, (lengths_ref[seq] + T - 1) // T)

        def each_page(seq, block, slot, act):
            """``act`` on the copies of the block's pages that hold a
            position under the sequence's length (one page at least)."""
            held = lengths_ref[seq] - block * T
            pages = jnp.clip((held + page - 1) // page, 1, ppb)

            def one(j, _):
                pid = table_ref[seq * W + block * ppb + j]
                rows = pl.ds(pl.multiple_of(j * page, page), page)
                for at, (pool, buf) in enumerate(zip(hbm, bufs)):
                    act(pltpu.make_async_copy(
                        pool.at[layer, pid], buf.at[slot, rows],
                        sems.at[at, slot]))
                return _
            jax.lax.fori_loop(0, pages, one, None)

        def start_next():
            """Start the copies of the chain's next block, if there is
            one, into the buffer of the block computed last."""
            seq, block = chain[SEQ], chain[BLOCK]

            @pl.when(seq < pl.num_programs(0))
            def _():
                each_page(seq, block, jax.lax.rem(chain[STARTED], buffers),
                          lambda copy: copy.start())
                chain[STARTED] += 1
                last = block + 1 >= blocks_of(seq)
                chain[SEQ] = jnp.where(last, seq + 1, seq)
                chain[BLOCK] = jnp.where(last, 0, block + 1)

        @pl.when(b == 0)
        def _():
            for at in (STARTED, SEQ, BLOCK, DONE):
                chain[at] = 0
            for _ in range(buffers - 1):
                start_next()

        # [Np, NKV*H]: whether a column is of the row's (query head's) own
        # K/V head
        own = jax.lax.div(jax.lax.broadcasted_iota(jnp.int32, (Np, D), 0),
                          rep) == jax.lax.div(
            jax.lax.broadcasted_iota(jnp.int32, (Np, D), 1), H)
        # the queries, block-diagonal
        if half:
            spread = q_ref[0]
        else:
            spread = jnp.concatenate([q_ref[0]] * NKV, axis=1)
            spread = jnp.where(own, spread, jnp.zeros_like(spread))

        def body(i, carry):
            m, l, acc = carry
            start_next()
            slot = jax.lax.rem(chain[DONE], buffers)
            each_page(b, i, slot, lambda copy: copy.wait())
            held = length - i * T

            @pl.when(held < T)
            def _():
                at = jax.lax.broadcasted_iota(jnp.int32, (T, D), 0)
                v = vbuf[slot]
                vbuf[slot] = jnp.where(at < held, v, jnp.zeros_like(v))

            scores = jax.lax.dot_general(
                spread, kbuf[slot], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale     # [Np, T]
            at = jax.lax.broadcasted_iota(jnp.int32, (Np, T), 1)
            scores = jnp.where(at < held, scores, _MASKED)
            m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
            p = jnp.exp(scores - m_new)
            alpha = jnp.exp(m - m_new)
            l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
            if latent:      # every head weighs the one row's values
                acc = alpha * acc + jnp.dot(
                    p.astype(vbuf.dtype), vbuf[slot, :, :columns],
                    preferred_element_type=jnp.float32)
            else:
                weighed = jnp.dot(p.astype(vbuf.dtype), vbuf[slot],
                                  preferred_element_type=jnp.float32)
                weighed = jnp.where(own, weighed, 0.0)          # [Np, D]
                acc = alpha * acc
                if half:                 # the row as it is, cut outside
                    acc = acc + weighed
                for k in range(0 if half else NKV):
                    # the diagonal blocks, side by side
                    acc = acc + weighed[:, k * H:(k + 1) * H]
            chain[DONE] += 1
            return m_new, l, acc

        _, l, acc = jax.lax.fori_loop(
            0, blocks_of(b), body,
            (jnp.full((Np, 1), _MASKED, jnp.float32),
             jnp.zeros((Np, 1), jnp.float32),
             jnp.zeros((Np, columns), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, Np, columns), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            in_specs=[pl.BlockSpec((1, Np, q.shape[2]),
                                   lambda b, *_: (b, 0, 0))]
            + [pl.BlockSpec(memory_space=pl.ANY) for _ in pools],
            out_specs=pl.BlockSpec((1, Np, columns),
                                   lambda b, *_: (b, 0, 0)),
            scratch_shapes=[pltpu.VMEM((buffers, T, D), pool.dtype)
                            for pool in pools]
            + [pltpu.SemaphoreType.DMA((len(pools), buffers)),
               pltpu.SMEM((4,), jnp.int32)],
            grid=(B,)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="paged_read")
    with kernel_source.nowhere():
        out = call(where, lengths, table, q, *pools)
    if half:     # head n's columns of its own K/V head; the others are 0
        return jnp.sum(out[:, :N].reshape(B, N, NKV, H), axis=2)
    return out[:, :N]
