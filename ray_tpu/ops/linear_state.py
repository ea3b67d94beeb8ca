"""The token step of a linear layer (``ops/linear_attention.py``: the gated
delta rule, one position for every slot of a decode batch) as a Pallas TPU
kernel that reads a slot's state ONCE and writes it where it lies.

The jnp forms (``gated_delta_step``, ``kda_step``) are two fusions over the
folded states, one for the two sums ``S^T k`` and ``S^T q`` and one that
reads the states again and writes the new ones, around which the model
slices a layer's slab out of the pool, selects the parked slots' old rows
back and writes the slab into the pool again: three state-sized streams
where the arithmetic needs two.  This kernel takes the POOL as it is stored,
``[L, B, panels, dk, 128]`` float32, aliased to its own result, with
``layer`` a prefetched scalar in the index maps of the blocks: a grid step
brings ``slots`` slots' panels of that layer into fast memory (45 x 96 x 128
x 4 B = 2.2 MB a slot at Olmo-Hybrid's shape, 32 x 128 x 128 x 4 B = 2.0 MB
at Kimi-Linear's; the pipeline holds the next block's copy-in and the last
one's copy-out beside it), takes both sums, ``w``, ``o`` and the new state
from that one copy, and the block goes back to the rows it came from.  No
other layer's rows, and no other array of the states' size, is touched or
made.  A parked slot (``live[b] == 0``) is copied through: its rows come
back to the bit, never recomputed.

ONE BODY FOR BOTH DECAYS.  The rule a panel, with ``a`` the decay of the
state's row i (a key channel's, Kimi Delta Attention; a head's scalar is a
head's channels all equal):

    S' = a_i S_ij                        the OLD state, decayed
    r_k = sum_i S'_ij k_i,  r_q = sum_i S'_ij q_i     both sums of it
    w_j = beta (v_j - r_k_j);   o_j = r_q_j + (k . q) w_j
    S_ij = S'_ij + k_i w_j

all float32 on the vector unit: products and sums of the values as they
are, no matrix unit, no rounding to bfloat16 anywhere.

WHAT BELONGS TO A HEAD reaches its lanes inside the kernel.  What varies
along a panel's ROWS (a, k, q: a value a key channel) comes as one operand
``cols`` [B, dk, 128]: the key channels down the sublanes, and along the
lanes every head's a, then every head's k, then every head's q (3 N of them
in one lane tile: 90 and 96 at the published shapes, 2% of the state's
bytes); a head's column is one lane of it, broadcast over the panel's lanes
in the kernel.  What varies along a panel's LANES (v, beta, k . q: a value a
column) comes as ``rows`` [B, 3, panels, 128], laid out as the panels are.
A panel of left-over columns holds ``side`` heads side by side
(``linear_attention._panel_plan``): its rows' values are a select between
those heads' broadcasts by the lane's head.  Neither a ``[.., dk, 128]``
array of spread keys nor a ``[.., dk, 1]`` operand (which the (8, 128) tiling
pads to the state's size) exists in HBM.

THE HEADS GO BY IN A LOOP, not unrolled: a turn is some whole groups of
heads (a group: the ``side`` heads of one panel of left-over columns with
their whole panels, or one head; ``_turn`` says how many), and the columns'
tile is turned once a turn by the turn's first head (one dynamic lane
rotation), after which the turn's columns lie in lanes known when the kernel
is traced; a panel's rows of ``rows`` and of the read-out are indexed by the
panel.  Unrolled, the hybrid's 45 panels were ~1,000 operations of kernel
body to trace once and to lower at every call site of every rung: +3 s of
``setup_s`` on the chip's host (PERF.md section 6, PR 52).

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
_SUBLANES = 8
# Slots a grid step.  On the chip (``scripts/linear_state_sweep.py``; PERF.md
# section 6, PR 52) 1, 2 and 4 slots read within 1.5% of each other at both
# published shapes (0.359 / 0.358 / 0.354 ms a layer at Olmo-Hybrid's, 0.438 /
# 0.437 / 0.438 at Kimi-Linear's), and a step with every slot parked, copy and
# nothing else, takes the same time: the block's way in and out binds, not
# its size nor the arithmetic.  One slot keeps the block's four buffers (in
# and out, each twice) under 9 MB.
_SLOTS = 1
# Panels a turn of the kernel's loop over the heads holds at most (a turn is
# a whole number of groups of heads, and the turns a whole number of them).
# A turn costs one dynamic rotation of the columns' tile: with one head (one
# panel) a turn Kimi-Linear's layer read 0.575 ms, with 2 / 4 / 8 / 16 / 32
# heads a turn 0.448 / 0.444 / 0.443 / 0.443 / 0.442 (32: no loop at all);
# Olmo-Hybrid's, a group two heads and three panels, 0.363 / 0.361 / 0.360 at
# 1 / 3 / 5 groups a turn (same sweep).  The body's length is what a program
# pays to trace it once and to lower it at every call site of every rung
# (PERF.md section 6, PR 52: ``setup_s``), so a turn is no longer than the
# readings ask.
_TURN_PANELS = 8
_VMEM_LIMIT_BYTES = 64 * 2 ** 20    # four slots a step hold 35 MB

# The kernel's module names no file (kernel_source.py says why).
kernel_source.exclude(__file__)


def supported(pool_shape, pool_dtype, heads: int, whole: int,
              side: int, shared: bool = False) -> bool:
    """Whether the kernel is written for this pool: float32 panels of whole
    128-lane tiles, a key channel a sublane of whole 8-sublane tiles, as many
    panels as ``heads`` heads fold into (``whole`` panels a head and one of
    left-over columns for every ``side`` heads), and every head's three
    columns in one lane tile (the kernel turns that tile as a whole);
    ``shared``: one key and one query for all heads, two columns whatever
    the heads."""
    if len(pool_shape) != 5:
        return False
    panels, dk, lanes = pool_shape[2:]
    return (jnp.dtype(pool_dtype) == jnp.float32 and lanes == _LANES
            and dk % _SUBLANES == 0 and (shared or 3 * heads <= _LANES)
            and panels == heads * whole + (heads // side if side else 0))


def _interpreted() -> bool:
    """Not ``kernel_source.kernels_compiled()`` (a test replaces this name
    on this module alone)."""
    return not kernel_source.kernels_compiled()


def _turn(groups: int, panels: int) -> int:
    """Groups of heads a turn of the loop: the most that divide ``groups``
    and hold ``_TURN_PANELS`` panels between them, ``panels`` a group."""
    return max(u for u in range(1, groups + 1)
               if groups % u == 0 and (u == 1 or u * panels <= _TURN_PANELS))


def columns(*parts):
    """The operand ``cols`` [B, dk, 128] of a, k, q [B, N, dk]: head n's a,
    k and q in lanes n, N + n and 2 N + n; of the SHARED rule's k, q [B, 1,
    dk]: lanes 0 and 1."""
    cols = jnp.concatenate(parts, axis=1).astype(jnp.float32)
    return jnp.swapaxes(
        jnp.pad(cols, ((0, 0), (0, _LANES - cols.shape[1]), (0, 0))), 1, 2)


def state_step(pool, layer, live, cols, rows, *, heads: int, whole: int,
               side: int, shared: bool = False,
               slots: Optional[int] = None,
               unroll: Optional[int] = None,
               interpret: Optional[bool] = None):
    """One position of the rule for every slot on layer ``layer`` of
    ``pool`` [L, B, panels, dk, 128] float32: ``live`` [B] (a slot that is
    not keeps its rows), ``cols`` [B, dk, 128] (``columns``), ``rows`` [B, 3,
    panels, 128] (v, beta and k . q as the panels lie), ``heads`` heads
    folded ``whole`` whole panels each and ``side`` to a panel of left-over
    columns (0: none are left over); ``shared`` (static, like the layout):
    the rule WITHOUT the correction, ``S <- a S + k v^T``, one key and one
    query a slot for all heads in lanes 0 and 1 of ``cols`` and a head's
    decay, a value a COLUMN, where ``rows`` has beta; ``slots`` slots a grid
    step and ``unroll`` groups of heads a turn of the loop (the module's
    choices unless a sweep or a test says).  Returns (o [B, panels, 128],
    the pool: its argument's buffer where the caller donates it)."""
    if not supported(pool.shape, pool.dtype, heads, whole, side, shared):
        raise ValueError(
            f"no linear-state kernel for a pool {pool.dtype}"
            f"{list(pool.shape)} of {heads} heads, {whole} whole panels a "
            f"head and {side} heads a panel of left-over columns")
    if interpret is None:
        interpret = _interpreted()
    slots = slots or _SLOTS
    # a group of heads: those of one panel of left-over columns, or one
    groups, panels = heads // (side or 1), (side or 1) * whole + bool(side)
    unroll = unroll or _turn(groups, panels)
    if pool.shape[1] % slots or groups % unroll:
        raise ValueError(
            f"{pool.shape[1]} slots are no whole number of blocks of "
            f"{slots}, or {groups} groups of heads no whole number of turns "
            f"of {unroll}")
    return _step(pool, jnp.asarray(layer, jnp.int32).reshape(1),
                 live.astype(jnp.int32), cols, rows, heads=heads,
                 whole=whole, side=side, shared=shared, slots=slots,
                 unroll=unroll, interpret=interpret)


# jitted and inlined where it is called, as ``paged_read._walk`` is: a
# model's programs trace the kernel once a shape, not once a call.
@functools.partial(jax.jit, static_argnames=("heads", "whole", "side",
                                             "shared", "slots", "unroll",
                                             "interpret"),
                   inline=True)
def _step(pool, where, live, cols, rows, *, heads: int, whole: int,
          side: int, shared: bool, slots: int, unroll: int,
          interpret: bool):
    _, B, panels, dk, W = pool.shape
    N, C = heads, cols.shape[2]
    # the heads of a group: those of one panel of left-over columns
    group = side or 1

    def kernel(where_ref, live_ref, cols_ref, rows_ref, s_ref, o_ref,
               out_ref):
        def panel(s, p, a, k, q):
            """Panel ``p`` of slot ``s`` under its rows' a, k, q [dk, W];
            the shared rule's a is None: the decay is a value a column, the
            second of the panel's rows, and nothing is corrected."""
            if a is None:
                v, a, kq = (rows_ref[s, i, pl.ds(p, 1), :] for i in range(3))
                decayed = a * s_ref[s, p]
                r_q = jnp.sum(decayed * q, axis=0, keepdims=True)
                o_ref[s, pl.ds(p, 1), :] = r_q + kq * v
                out_ref[s, p] = decayed + k * v
                return
            decayed = a * s_ref[s, p]
            r_k = jnp.sum(decayed * k, axis=0, keepdims=True)
            r_q = jnp.sum(decayed * q, axis=0, keepdims=True)
            v, beta, kq = (rows_ref[s, i, pl.ds(p, 1), :] for i in range(3))
            w = beta * (v - r_k)
            o_ref[s, pl.ds(p, 1), :] = r_q + kq * w
            out_ref[s, p] = decayed + k * w

        def step(s):
            lanes = jax.lax.broadcasted_iota(jnp.int32, (dk, W), 1)

            def one_group(m, at0, turned):
                """Group ``m``, whose first head's columns lie in lanes
                ``at0``, ``N + at0`` and ``2 N + at0`` of ``turned``; the
                shared rule's one key and query in lanes 0 and 1."""
                if shared:
                    k, q = (jnp.broadcast_to(turned[:, x:x + 1], (dk, W))
                            for x in range(2))
                    for j in range(group * whole):
                        panel(s, m * group * whole + j, None, k, q)
                    if side:
                        panel(s, N * whole + m, None, k, q)
                    return
                spread = []
                for at in range(group):
                    akq = [jnp.broadcast_to(
                        turned[:, x * N + at0 + at:x * N + at0 + at + 1],
                        (dk, W)) for x in range(3)]
                    for j in range(whole):
                        panel(s, (m * group + at) * whole + j, *akq)
                    spread.append(akq)
                if side:
                    # the group's panel of left-over columns: a lane's
                    # values are those of the head its column belongs to
                    akq = spread[0]
                    for at in range(1, side):
                        own = lanes >= at * (W // side)
                        akq = [jnp.where(own, new, old)
                               for new, old in zip(spread[at], akq)]
                    panel(s, N * whole + m, *akq)

            def one_turn(t, _):
                # the turn's first head's a to lane 0 (one dynamic lane
                # rotation of the tile): its heads' a, k, q then lie in
                # lanes known here, at, N + at, 2 N + at
                first = t * unroll * group
                turned = cols_ref[s] if shared else \
                    pltpu.roll(cols_ref[s], C - first, 1)
                for u in range(unroll):
                    one_group(t * unroll + u, u * group, turned)
                return _
            jax.lax.fori_loop(0, N // group // unroll, one_turn, None)

        for s in range(slots):
            is_live = live_ref[pl.program_id(0) * slots + s] != 0

            @pl.when(is_live)
            def _():
                step(s)

            @pl.when(jnp.logical_not(is_live))
            def _():
                out_ref[s] = s_ref[s]
                o_ref[s] = jnp.zeros((panels, W), jnp.float32)

    def state_block(b, where_ref, live_ref):
        return where_ref[0], b, 0, 0, 0

    call = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((B, panels, W), jnp.float32),
                   jax.ShapeDtypeStruct(pool.shape, pool.dtype)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            in_specs=[
                pl.BlockSpec((slots, dk, C), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((slots, 3, panels, W),
                             lambda b, *_: (b, 0, 0, 0)),
                pl.BlockSpec((None, slots, panels, dk, W), state_block)],
            out_specs=[
                pl.BlockSpec((slots, panels, W), lambda b, *_: (b, 0, 0)),
                pl.BlockSpec((None, slots, panels, dk, W), state_block)],
            grid=(B // slots,)),
        # the pool (after the two scalars, the columns and the rows) is the
        # second result: only the blocks of ``layer`` are ever written
        input_output_aliases={4: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="linear_state")
    with kernel_source.nowhere():
        return call(where, live, cols, rows, pool)
