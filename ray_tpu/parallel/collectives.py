"""Thin functional collectives for use inside shard_map-ped code.

Reference analogue: `ray.util.collective` op surface (allreduce/allgather/
reducescatter/broadcast/send/recv/barrier, `util/collective/collective.py:
258-615`).  There the ops are runtime NCCL calls between actor processes; here
they are `jax.lax` primitives that XLA lowers to ICI collectives inside a
compiled program.  The host-driven, actor-to-actor veneer with the reference's
exact API shape lives in `ray_tpu.util.collective`.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def all_reduce(x, axis_name: str, op: str = "sum"):
    if op == "sum":
        return lax.psum(x, axis_name)
    if op == "mean":
        return lax.pmean(x, axis_name)
    if op == "max":
        return lax.pmax(x, axis_name)
    if op == "min":
        return lax.pmin(x, axis_name)
    raise ValueError(f"unknown reduce op {op!r}")


def all_gather(x, axis_name: str, axis: int = 0, tiled: bool = True):
    return lax.all_gather(x, axis_name, axis=axis, tiled=tiled)


def psum_scatter(x, axis_name: str, scatter_dimension: int = 0):
    """Reduce-scatter: the building block of efficient DP gradient sync."""
    return lax.psum_scatter(
        x, axis_name, scatter_dimension=scatter_dimension, tiled=True)


def all_to_all(x, axis_name: str, split_axis: int, concat_axis: int):
    """Ulysses-style head<->sequence reshuffle, MoE token dispatch."""
    return lax.all_to_all(
        x, axis_name, split_axis=split_axis, concat_axis=concat_axis,
        tiled=True)


def ppermute_ring(x, axis_name: str, shift: int = 1):
    """Rotate shards around the ring — the ring-attention KV step."""
    n = lax.psum(1, axis_name)
    perm = [(i, (i + shift) % n) for i in range(n)]
    return lax.ppermute(x, axis_name, perm=perm)


def barrier_sum(axis_name: str):
    """Cheapest full-axis synchronization inside a program."""
    return lax.psum(jnp.zeros((), jnp.int32), axis_name)


# ------------------------------------------- tp's activation sums, in chunks
#
# A transformer block's two projection pairs (qkv -> attention -> out; wi ->
# GELU -> wo) under tensor parallelism: the residual stream between them lies
# sharded along the SEQUENCE over ("sp", "tp"), the pair's first product
# gathers the rows over `tp` and its last scatters the partial sums, and both
# move the rows as chunks around a ring of `tp` steps (`lax.ppermute` under a
# `shard_map` that is manual over tp, and over sp where the mesh has one,
# every other axis the compiler's) while the product of the chunk at hand
# runs.  Written `a + b` either way: the same operands and the same partial
# sums as an all-reduce after the last product, in two halves that each
# travel beside a product.  Chunks are rows of the sequence, never a
# contraction.  The transposes that autodiff makes of the two rings are each
# other (a gather's is a scatter).
#
# The compiler schedules a permute's start as early as it can and knows
# nothing of what then waits behind it: on the chip a SYNC collective issued
# after a start (a weight's fsdp gather, a bias gradient's sum) takes as long
# as the permute has left, whatever its own size, and the products that need
# it wait too.  Two orderings are therefore written as data dependencies
# (`optimization_barrier`): a gather's rows leave once its weights are whole
# (`_weights_first`), and a cotangent goes on to the ring behind a bias once
# that bias's gradient is summed (`add_bias_first`).

_SEQ_AXES = ("sp", "tp")


def tp_size(mesh) -> int:
    """How many ways ``mesh`` shards a layer's heads and hidden units."""
    return dict(mesh.shape).get("tp", 1)


def _ring_specs(eq: str, shard: str, gathered: tuple, mesh):
    """(operand letters, specs over the manual axes, those axes) of an
    einsum whose letter ``s`` is the sequence and ``shard`` what tp splits;
    ``gathered`` says which of (lhs, out) hold the rows of all of tp."""
    ins, ol = eq.split("->")
    xl, wl = ins.split(",")
    # sp is manual only where it splits something: a weight's cotangent
    # is summed over every manual axis its spec does not name
    manual = tuple(a for a in _SEQ_AXES if dict(mesh.shape).get(a, 1) > 1)
    whole = tuple(a for a in manual if a != "tp") or None

    def spec(letters, is_gathered):
        return P(*[(whole if is_gathered else manual) if c == "s"
                   else "tp" if c == shard else None for c in letters])

    return ((xl, wl, ol),
            (spec(xl, gathered[0]), P(*["tp" if c == shard else None
                                        for c in wl]),
             spec(ol, gathered[1])),
            frozenset(manual))


@jax.custom_vjp
def _weights_first(x, w):
    """``(x, w)`` with ``w`` whole over the compiler's axes (its fsdp
    gather) before ``x`` may be used: the gather runs ahead of the permute
    that takes ``x`` away and not behind it.  Forward only: the cotangents
    pass as they are."""
    return lax.optimization_barrier(
        (x, lax.with_sharding_constraint(w, P())))


_weights_first.defvjp(lambda x, w: (_weights_first(x, w), None),
                      lambda _, cotangents: cotangents)


@jax.custom_vjp
def add_bias_first(y, b):
    """``y + b`` for a ``b`` broadcast over ``y``'s rows, whose backward
    sums ``b``'s gradient (over the rows' shards too: a small SYNC
    all-reduce) BEFORE ``y``'s cotangent goes on to the ring that made
    ``y``.  The same sums as ``y + b``'s own transpose, in that order."""
    return y + b


def _add_bias_bwd(_, g):
    db = lax.reduce(g, jnp.zeros((), g.dtype), lax.add,
                    tuple(range(g.ndim - 1)))
    return lax.optimization_barrier((g, db))


add_bias_first.defvjp(lambda y, b: (y + b, None), _add_bias_bwd)


def gathered_einsum(eq: str, x, w, shard: str, mesh, by_step: bool = False):
    """``einsum(eq, x, w)`` where ``x``'s rows (letter ``s``) lie sharded
    over ("sp", "tp") and ``w``'s letter ``shard`` over tp: the result holds
    the rows of all of tp (``s`` over sp alone) and ``shard`` over tp.  The
    all-gather is folded into the product: a device's rows go on to its
    neighbour while their own product runs, tp steps in all.  ``by_step``
    leaves the rows as the ring brought them, a tuple of tp results (step
    i: the rows of device me - i), for what follows row by row and ends in
    ``scattered_einsum(by_step=True)``: nothing is put in order."""
    (xl, _, ol), specs, manual = _ring_specs(eq, shard, (False, True), mesh)
    at = ol.index("s")

    def ring(x, w):
        n, me = lax.axis_size("tp"), lax.axis_index("tp")
        x, w = _weights_first(x, w)
        steps = []
        for i in range(n):
            coming = ppermute_ring(x, "tp") if i + 1 < n else None
            steps.append(jnp.einsum(eq, x, w))   # the rows of device me - i
            x = coming
        if by_step:
            return tuple(steps)
        # in order: device p's rows came at step me - p
        return jnp.concatenate(
            [lax.select_n((me - p) % n, *steps) for p in range(n)], at)

    return jax.shard_map(
        ring, mesh=mesh, in_specs=specs[:2], axis_names=manual,
        out_specs=(specs[2],) * tp_size(mesh) if by_step else specs[2],
        check_vma=False)(x, w)


def scattered_einsum(eq: str, h, w, shard: str, mesh, by_step: bool = False):
    """``einsum(eq, h, w)`` contracting the letter ``shard`` that tp splits,
    of an ``h`` that holds the rows (letter ``s``) of all of tp (in order,
    or ``by_step`` as ``gathered_einsum`` leaves them): the partial sums are
    added across tp and the result's rows left sharded over ("sp", "tp").
    The reduce-scatter is folded into the product: the sum for device
    me - 1 - i is on its way while the next rows' product runs, and a
    device's own rows come last, onto what the ring brought."""
    (hl, _, _), specs, manual = _ring_specs(eq, shard, (True, False), mesh)
    at = hl.index("s")

    def ring(h, w):
        n, me = lax.axis_size("tp"), lax.axis_index("tp")
        rows = h[0].shape[at] if by_step else h.shape[at] // n
        acc = None
        for i in range(n):
            arriving = None if acc is None else ppermute_ring(acc, "tp")
            part = jnp.einsum(eq, h[(i + 1) % n] if by_step else
                              lax.dynamic_slice_in_dim(
                                  h, ((me - 1 - i) % n) * rows, rows, at), w)
            if arriving is not None:
                # the add stays out of the product's fusion: fused, the
                # product would wait for the sum that travels beside it
                part = lax.optimization_barrier(part) + arriving
            acc = part
        return acc

    return jax.shard_map(
        ring, mesh=mesh, axis_names=manual, out_specs=specs[2],
        in_specs=((specs[0],) * tp_size(mesh) if by_step else specs[0],
                  specs[1]), check_vma=False)(h, w)
