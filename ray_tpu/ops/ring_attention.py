"""Ring attention: causal attention with the sequence sharded over a mesh axis.

New capability vs. the reference (SURVEY §5.7 — Ray has *no* sequence/context
parallelism; sequence length is bounded by one GPU's memory).  Here each
device of the `sp` axis holds one contiguous sequence chunk of Q/K/V; K/V
chunks rotate around the ICI ring via `lax.ppermute` while every device
accumulates blockwise online-softmax partial results for its local queries.
Peak memory per device is O(S/sp), and with sp devices the compute/comm
pipeline overlaps (XLA schedules the ppermute DMA alongside the matmuls).

`ulysses_attention` is the all-to-all alternative (DeepSpeed-Ulysses layout):
reshuffle [seq-sharded, all heads] -> [all seq, head-sharded], run any dense
kernel per head group, and shuffle back.  Cheaper at moderate sequence
lengths; ring wins at very long context.

Both are written against a bare `axis_name`, so they run identically inside
`shard_map` on the CPU test mesh and on a real slice.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

_NEG_INF = -1e30


def _block_update(q, k, v, m, l, o, mask):
    """One online-softmax accumulation step. q:[B,Sq,N,H] k,v:[B,Sk,N,H],
    mask:[Sq,Sk] bool or None; carries m,l:[B,N,Sq,1], o:[B,Sq,N,H]."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqnh,bknh->bnqk", q, k).astype(jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask[None, None], s, _NEG_INF)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m - m_new)  # [B,N,Sq,1]
    l_new = l * alpha + jnp.sum(p, axis=-1, keepdims=True)
    o_new = o * jnp.moveaxis(alpha, 1, 2) + jnp.moveaxis(
        jnp.einsum("bnqk,bknh->bnqh", p, v.astype(jnp.float32)), 1, 2)
    return m_new, l_new, o_new


def ring_attention_sharded(q, k, v, axis_name: str = "sp"):
    """Causal ring attention; call inside shard_map with seq sharded on
    `axis_name`.  q,k,v: per-device [B, S_local, N, H] chunks (chunk i holds
    positions [i*S_local, (i+1)*S_local))."""
    B, Sq, N, H = q.shape
    sp = lax.axis_size(axis_name)
    my = lax.axis_index(axis_name)

    m = jnp.full((B, N, Sq, 1), _NEG_INF, jnp.float32)
    l = jnp.zeros((B, N, Sq, 1), jnp.float32)
    o = jnp.zeros((B, Sq, N, H), jnp.float32)

    causal_mask = jnp.tril(jnp.ones((Sq, Sq), bool))
    ones_mask = jnp.ones((Sq, Sq), bool)
    zeros_mask = jnp.zeros((Sq, Sq), bool)

    def step(i, carry):
        m, l, o, k, v = carry
        # kv chunk currently held arrived from device (my - i) mod sp
        src = (my - i) % sp
        # causal relation of my q-chunk vs. this kv-chunk:
        #   src < my  -> full attention; src == my -> causal; src > my -> skip
        mask = jnp.where(
            src == my, causal_mask, jnp.where(src < my, ones_mask,
                                              zeros_mask))
        m, l, o = _block_update(q, k, v, m, l, o, mask)
        perm = [(d, (d + 1) % sp) for d in range(sp)]
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        return m, l, o, k, v

    m, l, o, _, _ = lax.fori_loop(0, sp, step, (m, l, o, k, v))
    out = o / jnp.maximum(jnp.moveaxis(l, 1, 2), 1e-30)
    return out.astype(q.dtype)


def ring_attention(q, k, v, mesh, axis_name: str = "sp",
                   batch_axes=("dp", "fsdp"), head_axis: Optional[str] = "tp"):
    """Driver-side wrapper: shard_map `ring_attention_sharded` over `mesh`.

    q,k,v: global [B, S, N, H].  Sequence is sharded over `axis_name`, batch
    over `batch_axes`, heads over `head_axis`.
    """
    from jax.sharding import PartitionSpec as P

    spec = P(tuple(batch_axes), axis_name, head_axis, None)
    fn = jax.shard_map(
        functools.partial(ring_attention_sharded, axis_name=axis_name),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec,
        check_vma=False)
    return fn(q, k, v)


def ulysses_attention(q, k, v, axis_name: str = "sp", causal: bool = True):
    """All-to-all (DeepSpeed-Ulysses) attention; call inside shard_map.

    In: per-device [B, S/sp, N, H] (seq sharded).  all_to_all to
    [B, S, N/sp, H] (heads sharded), dense attention locally, all_to_all
    back.  Requires N % sp == 0.
    """
    sp = lax.axis_size(axis_name)
    # [B, S/sp, N, H] -> heads sharded, seq gathered
    qh = lax.all_to_all(q, axis_name, split_axis=2, concat_axis=1, tiled=True)
    kh = lax.all_to_all(k, axis_name, split_axis=2, concat_axis=1, tiled=True)
    vh = lax.all_to_all(v, axis_name, split_axis=2, concat_axis=1, tiled=True)
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqnh,bknh->bnqk", qh, kh).astype(jnp.float32) * scale
    if causal:
        S = qh.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(qh.dtype)
    oh = jnp.einsum("bnqk,bknh->bqnh", p, vh)
    return lax.all_to_all(oh, axis_name, split_axis=1, concat_axis=2,
                          tiled=True)


def make_ring_attention_fn(mesh, axis_name: str = "sp",
                           batch_axes=("dp", "fsdp"),
                           head_axis: Optional[str] = "tp"):
    """Close over the mesh/axis topology once and return an
    `(q, k, v) -> o` callable with the plain attention signature.  Raises
    ValueError up front when the mesh cannot carry a ring (no `axis_name` axis, or size 1 — a 1-wide ring is just
    dense attention with extra collectives)."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    sp = sizes.get(axis_name, 1)
    if sp <= 1:
        raise ValueError(
            f"ring attention needs mesh axis {axis_name!r} with size > 1 "
            f"(got {sizes})")

    def fn(q, k, v):
        return ring_attention(q, k, v, mesh, axis_name=axis_name,
                              batch_axes=batch_axes, head_axis=head_axis)
    return fn
