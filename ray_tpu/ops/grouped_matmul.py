"""Grouped matmul over one layer's groups of a stack of layers, as a Pallas
TPU kernel.

``lhs`` [m, K] holds rows sorted by group; group g's rows are multiplied by
``rhs[layer * G + g]``, where ``rhs`` [L * G, K, N] is the stack of every
layer's G matrices AS IT IS STORED (a reshape of the parameter: no copy, no
cast) and ``group_sizes`` [G] are this layer's alone.  ``layer`` may be
traced (the index of a ``lax.scan`` over the layers): it is prefetched as a
scalar and added in the weights' index map, so the other layers' matrices
do not exist for the kernel, and neither does a group without rows.

The grid is (tiles of N, visits): a visit is one (group, row tile) pair
with rows in it, listed group by group, so the weights' block index changes
only when the group does and each touched matrix crosses from HBM once per
tile of N (Pallas skips the copy of a block whose index did not change,
and double-buffers the rest).  K is never split: the work this serves is
bound by the bytes of the weights at a few rows a group, and a [K, tn] tile
of megabytes moves them in few large transfers.  A row tile that two groups
share is visited by both in turn; each stores only its own rows.

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
_MAX_ROW_TILE = 512


# The kernel's module names no file (kernel_source.py says why).
kernel_source.exclude(__file__)


def _tiles(m: int, K: int, N: int, itemsize: int, groups: int) -> tuple:
    """(tm, tn) from what the call can observe.  ``tn``: the widest whole
    number of 128-lane columns that divides N with a [K, tn] tile inside
    ``_WEIGHT_TILE_BYTES`` (all of N when N is no multiple of 128: a toy
    size).  ``tm``: the mean rows a group, as a power of two between 128
    (up to which a visit costs the MXU the same, because it is loading the
    weights, so fewer and larger row tiles mean fewer visits: at 8 rows a
    group row tiles of 16 took 2.05 ms where 128 take 1.42) and
    ``_MAX_ROW_TILE`` (the prefill's hundreds of rows a group), and no more
    than the rows there are, in whole sublane tiles of the type."""
    tn = N
    if N % _LANES == 0:
        fits = [t for t in range(_LANES, N + 1, _LANES)
                if N % t == 0 and K * t * itemsize <= _WEIGHT_TILE_BYTES]
        tn = max(fits, default=_LANES)
    sublanes = 32 // itemsize            # rows of one packed [8, 128] tile
    tm = _LANES
    while tm < _MAX_ROW_TILE and tm * groups < m:
        tm *= 2
    return min(tm, -(-m // sublanes) * sublanes), tn


def _resolve(m, K, N, itemsize, groups, interpret):
    if interpret is None:
        # The interpreter is for the CPU backend, where the tests run.
        interpret = jax.default_backend() == "cpu"
    return (*_tiles(m, K, N, itemsize, groups), interpret)


def _visits(group_sizes, tiles_m: int, tm: int):
    """The (group, row tile) pairs that hold rows, group by group.  Returns
    (offsets [G + 1]: the row each group starts at; group_ids, tile_ids
    [tiles_m + G - 1]: the pair of each visit, the entries past ``count``
    unused; count: a group's rows end in at most one tile that the next
    group starts in, hence the bound)."""
    G = group_sizes.shape[0]
    ends = jax.lax.cumsum(group_sizes)
    starts = ends - group_sizes
    first_tile = starts // tm
    per_group = jnp.where(group_sizes > 0,
                          (ends + tm - 1) // tm - first_tile, 0)
    visit_ends = jax.lax.cumsum(per_group)
    visit = jax.lax.iota(jnp.int32, tiles_m + G - 1)
    # a visit's group: the groups whose visits have all gone before it
    group_ids = jnp.minimum(G - 1, jnp.sum(
        visit[:, None] >= visit_ends[None, :], axis=1, dtype=jnp.int32))
    tile_ids = first_tile[group_ids] + visit - (
        visit_ends - per_group)[group_ids]
    return jnp.pad(ends, (1, 0)), group_ids, \
        jnp.minimum(tile_ids, tiles_m - 1), visit_ends[-1]


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
    tm, tn, interpret = _resolve(m, K, rhs.shape[2], lhs.dtype.itemsize, G,
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
    offsets, group_ids, tile_ids, count = _visits(group_sizes, tiles_m, tm)

    def kernel(offsets, group_ids, tile_ids, layer, lhs_ref, rhs_ref,
               out_ref):
        visit = pl.program_id(1)
        group = group_ids[visit]
        rows = tile_ids[visit] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, tn), 0)
        mine = (rows >= offsets[group]) & (rows < offsets[group + 1])
        product = jnp.dot(lhs_ref[...], rhs_ref[...],
                          preferred_element_type=jnp.float32)
        out_ref[...] = jnp.where(
            mine, product, out_ref[...].astype(jnp.float32)
        ).astype(out_ref.dtype)

    def lhs_block(n, visit, offsets, group_ids, tile_ids, layer):
        return tile_ids[visit], 0

    def rhs_block(n, visit, offsets, group_ids, tile_ids, layer):
        return layer[0] * G + group_ids[visit], 0, n

    def out_block(n, visit, offsets, group_ids, tile_ids, layer):
        return tile_ids[visit], n

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((tiles_m * tm, N), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            in_specs=[pl.BlockSpec((tm, K), lhs_block),
                      pl.BlockSpec((None, K, tn), rhs_block)],
            out_specs=pl.BlockSpec((tm, tn), out_block),
            grid=(N // tn, count)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_matmul")
    with kernel_source.nowhere():
        out = call(offsets, group_ids, tile_ids, layer, lhs, rhs)
    return out[:m]
