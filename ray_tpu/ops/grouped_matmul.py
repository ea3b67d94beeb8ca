"""Grouped matmul over one layer's groups of a stack of layers, as a Pallas
TPU kernel.

``lhs`` [m, K] holds rows sorted by group; group g's rows are multiplied by
``rhs[layer * G + g]``, where ``rhs`` [L * G, K, N] is the stack of every
layer's G matrices AS IT IS STORED (a reshape of the parameter: no copy, no
cast) and ``group_sizes`` [G] are this layer's alone.  ``layer`` may be
traced (the index of a ``lax.scan`` over the layers): it is prefetched as a
scalar and added to the group where the weights are copied from, so the
other layers' matrices do not exist for the kernel, and neither does a
group without rows.

The grid is (tiles of N, visits): a visit is one (group, row tile) pair
with rows in it, listed group by group, so each touched matrix crosses from
HBM once per tile of N.  K is never split: the work this serves is bound by
the bytes of the weights at a few rows a group, and a [K, tn] tile of
megabytes moves them in few large transfers.  A row tile that two groups
share is visited by both in turn, each multiplying the whole tile and
storing only its own rows: so the row tile is 128 rows and does not grow
with the rows (``_tiles``).  Rows past the groups' total (``moe_dropless``
sorts there the assignments that are nobody's: padding, idle slots, experts
held elsewhere) are in no visit and cost nothing.

The weights stay in HBM and the kernel copies them itself, into two buffers
in turn: a group's FIRST visit waits for its own tile and sends for the
next group's, which then has all of this group's visits to arrive in.
(Handed to Pallas as a block, the next group's tile is sent for at the
start of the current group's LAST visit, and at a prefill's hundreds of rows
a group, where the copy and the group's products take about as long, all the
earlier visits' products went unhidden: PERF.md section 6, PR 56.)  The rows
and the results are Pallas' blocks as before.

Modelled on ``jax.experimental.pallas.ops.tpu.megablox.gmm`` (group
metadata, masked store), trimmed to what ``ops/moe.py::moe_dropless`` needs:
forward only, no sharded groups, no transposed weights, no split of K.

On the CPU backend the kernel runs in Pallas interpret mode.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kernel_source

# Fast memory the kernel asks for (a v5e core has 128 MiB), and the most one
# buffer of the weights' tile may take of it: the tile is double-buffered,
# beside the row tile, the output tile and the float32 product.  On the chip
# (PERF.md section 6, PR 41) tiles of 2, 4 and 8 MiB read alike, 84-90% of
# the HBM's rate; 8 takes every expert of the three served models whole.
_VMEM_LIMIT_BYTES = 64 * 2 ** 20
_WEIGHT_TILE_BYTES = 8 * 2 ** 20
_LANES = 128
_ROW_TILE = 128


# The kernel's module names no file (kernel_source.py says why).
kernel_source.exclude(__file__)


def _tiles(m: int, K: int, N: int, itemsize: int) -> tuple:
    """(tm, tn) from what the call can observe.  ``tn``: the widest whole
    number of 128-lane columns that divides N with a [K, tn] tile inside
    ``_WEIGHT_TILE_BYTES`` (all of N when N is no multiple of 128: a toy
    size).  ``tm``: ``_ROW_TILE`` rows however many there are, and no more
    than the rows there are, in whole sublane tiles of the type.  Up to
    128 rows a visit costs the MXU the same, because it is loading the
    weights (at 8 rows a group row tiles of 16 took 2.05 ms where 128 take
    1.42).  Past them a larger tile costs rows: a group's weights are
    fetched when the GROUP changes, not when the tile does, and a tile that
    two groups share is multiplied whole by both, so the work is (tiles +
    groups - 1) visits of ``tm`` rows.  On the chip at LFM2's prefill (256
    rows a group in the mean, ragged) a layer's two products and SwiGLU
    took 4.18 ms at 128, 4.55 at 256, 6.07 at 512 and 4.47 at 64 (PERF.md
    section 6, PR 56, where the sweep is)."""
    tn = N
    if N % _LANES == 0:
        fits = [t for t in range(_LANES, N + 1, _LANES)
                if N % t == 0 and K * t * itemsize <= _WEIGHT_TILE_BYTES]
        tn = max(fits, default=_LANES)
    sublanes = 32 // itemsize            # rows of one packed [8, 128] tile
    return min(_ROW_TILE, -(-m // sublanes) * sublanes), tn


def _resolve(m, K, N, itemsize, interpret):
    if interpret is None:
        interpret = not kernel_source.kernels_compiled()
    return (*_tiles(m, K, N, itemsize), interpret)


def _visits(group_sizes, tiles_m: int, tm: int):
    """The (group, row tile) pairs that hold rows, group by group.  Returns
    (offsets [G + 1]: the row each group starts at; group_ids, tile_ids
    [tiles_m + G - 1]: the pair of each visit, the entries past ``count``
    unused; count: a group's rows end in at most one tile that the next
    group starts in, hence the bound; chain [3 * G]: for each group its
    first visit, the next group that has rows (G: none) and which of the
    two buffers its weights are fetched into, one list after the other)."""
    G = group_sizes.shape[0]
    ends = jax.lax.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // tm
    has_rows = group_sizes > 0
    per_group = jnp.where(has_rows, (ends + tm - 1) // tm - first_tile, 0)
    visit_ends = jax.lax.cumsum(per_group)
    visit = jax.lax.iota(jnp.int32, tiles_m + G - 1)
    # a visit's group: the groups whose visits have all gone before it
    group_ids = jnp.minimum(G - 1, jnp.sum(
        visit[:, None] >= visit_ends[None, :], axis=1, dtype=jnp.int32))
    tile_ids = first_tile[group_ids] + visit - (
        visit_ends - per_group)[group_ids]
    with_rows = jnp.where(has_rows, jax.lax.iota(jnp.int32, G), G)
    following = jnp.pad(jax.lax.cummin(with_rows, reverse=True)[1:], (0, 1),
                        constant_values=G)
    chain = jnp.concatenate([
        visit_ends - per_group, following,
        (jax.lax.cumsum(has_rows.astype(jnp.int32)) - 1) % 2])
    return jnp.pad(ends, (1, 0)), group_ids, \
        jnp.minimum(tile_ids, tiles_m - 1), visit_ends[-1], chain


def grouped_matmul(lhs, rhs, group_sizes, layer, *,
                   interpret: Optional[bool] = None):
    """lhs [m, K] sorted by group, rhs [L * G, K, N] of lhs's type,
    group_sizes [G] int32 (this layer's), layer a scalar index -> [m, N] in
    that type, products accumulated in float32.  Rows past the groups'
    total are not computed (their result is undefined)."""
    m, K = lhs.shape
    G = group_sizes.shape[0]
    if rhs.shape[0] % G or rhs.shape[1] != K or lhs.dtype != rhs.dtype:
        raise ValueError(
            f"rows {lhs.dtype}{list(lhs.shape)} and {G} groups do not go "
            f"with a stack {rhs.dtype}{list(rhs.shape)}")
    tm, tn, interpret = _resolve(m, K, rhs.shape[2], lhs.dtype.itemsize,
                                 interpret)
    return _tiled(lhs, rhs, group_sizes.astype(jnp.int32),
                  jnp.asarray(layer, jnp.int32).reshape(1),
                  tm=tm, tn=tn, interpret=interpret)


# jitted, and inlined where it is called: a model's programs (a serving
# engine compiles a dozen, each with two calls a layer scan) then trace the
# metadata and the kernel once a shape, not once a call; a replica's set-up
# is mostly the interpreter's time under one lock (PERF.md section 6, PR 35
# and PR 41: traced at every call, the kernel cost a replica's start 9%).
@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"),
                   inline=True)
def _tiled(lhs, rhs, group_sizes, layer, *, tm: int, tn: int,
           interpret: bool):
    m, K = lhs.shape
    G = group_sizes.shape[0]
    N = rhs.shape[2]
    tiles_m = -(-m // tm)
    if tiles_m * tm != m:
        lhs = jnp.pad(lhs, ((0, tiles_m * tm - m), (0, 0)))
    offsets, group_ids, tile_ids, count, chain = _visits(
        group_sizes, tiles_m, tm)

    def kernel(offsets, group_ids, tile_ids, layer, chain, lhs_ref, rhs_ref,
               out_ref, weights, arrived):
        n, visit = pl.program_id(0), pl.program_id(1)
        group = group_ids[visit]
        following, held = chain[G + group], chain[2 * G + group]

        def fetch(group, into):
            matrix = rhs_ref.at[layer[0] * G + group]
            if tn != N:                  # (a whole matrix is one piece)
                matrix = matrix.at[:, pl.ds(n * tn, tn)]
            return pltpu.make_async_copy(matrix, weights.at[into],
                                         arrived.at[into])

        @pl.when(visit == 0)
        def _():
            fetch(group, held).start()

        # A group's first visit sends for the next group's weights, which
        # then have all of this group's visits to arrive in (Pallas' own
        # pipeline would send for them at the last), and waits for its own.
        @pl.when(visit == chain[group])
        def _():
            @pl.when(following < G)
            def _():
                fetch(following, 1 - held).start()

            fetch(group, held).wait()

        rows = tile_ids[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (rows >= offsets[group]) & (rows < offsets[group + 1])
        product = jnp.dot(lhs_ref[...], weights[held],
                          preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(
            mine, product, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    def lhs_block(n, visit, offsets, group_ids, tile_ids, layer, chain):
        return tile_ids[visit], 0

    def out_block(n, visit, offsets, group_ids, tile_ids, layer, chain):
        return tile_ids[visit], n

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((tiles_m * tm, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            in_specs=[pl.BlockSpec((tm, K), lhs_block),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((tm, tn), out_block),
            grid=(N // tn, count),
            scratch_shapes=[pltpu.VMEM((2, K, tn), rhs.dtype),
                            pltpu.SemaphoreType.DMA((2,))]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_matmul")
    with kernel_source.nowhere():
        out = call(offsets, group_ids, tile_ids, layer, chain, lhs, rhs)
    return out[:m]
