"""A prompt CHUNK's latent attention over the sequence's pages, as one Pallas
TPU kernel whose scores never leave fast memory.

``models/llama.py::llama_prefill`` with a ``start`` runs the positions
``start .. start + S - 1`` of a prompt (``length`` of them real) against
everything the sequence's latent pages hold under ``start + length``: what
earlier chunks left there and the chunk's own rows.  The caller expands the
table's rows per head ONCE a layer (``wkv_b``: keys ``[T, N x dn]``, values
``[T, N x dv]``, T the table's positions, a head a run of columns of a
position's row: two plain matrix products) and hands them over with the
rotated key all heads share (``[T, dr]``) and, for a model with an indexer,
the selection ``keep [S, T]``: a mask a QUERY that all heads share and that
already holds the causal bound (``ops/paged_attention.py::select_mask``).
Without one the mask is the causal bound, made from the positions.

``latent_chunk_attention`` is the entry and the kernel is all there is.  What
it can observe decides only how the kernel is run (``_compiled``): on a
backend other than the CPU, with a head's unrotated keys and its values whole
128-lane tiles and blocks of whole tiles, it is compiled (Mosaic); anywhere
else (the CPU's tests at small widths) the same kernel runs in Pallas
interpret mode.

THE WALK: queries outermost.  A block of ``block_q`` queries meets the
blocks of ``block_k`` keys it may see in order, with an online softmax whose running maximum, sum and ``[block_q, dv]`` accumulator belong to the
one block of queries in hand and are divided and written once, after its
last block of keys.  The loop's bound, not a mask, ends a block of queries'
walk at the block of keys that holds ``start +`` its last real query (a
block of nothing but padding reads nothing and gives zeros); inside the
bound the mask decides.  The arithmetic is ``_mla_expanded``'s: scores are
the float32 sum of the two products of the stored operands (``q_nope . k``
over ``dn`` and ``q_rope . k_rope`` over ``dr``) times
``sm_scale``; a masked score is -1e30 before the maximum and its
probability exactly 0 after the exponential, so a query that keeps nothing
of a block adds nothing; the statistics are float32; the probabilities are
rounded to the values' type for their product, which accumulates in
float32; the division comes last.

THE KERNEL.  Operands stay token-major as the model makes them, a head a run
of whole lane tiles of a row (``[S, N x dn]``, ``[T, N x dn]``, ``[T, N x
dv]``, the result ``[S, N x dv]``: no transposed copy of anything but the
narrow rotated queries, which go head-major).  The grid is (block of
queries, group of ``heads`` heads, block of keys), keys innermost; the
statistics and the accumulator of the group's heads are scratch carried over
the innermost dimension.  ``start`` and ``length`` are prefetched scalars:
the index maps hold a block of queries' walk at its last visible block of
keys (a block whose index does not change is not copied again) and the body
of a step past it does not run.  The mask's tile is fetched once a STEP and
shared by the group's heads (int8: a tile of 512 x 1024 is a sixteenth of
the step's keys and values at eight heads); with ``keep`` None no mask is
read.  The ``[block_q, block_k]`` scores, their probabilities and the casts
exist only in fast memory: nothing of the softmax crosses HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kernel_source
# bound here by this name: a test replaces it on this module alone
from ray_tpu.ops.kernel_source import kernels_compiled as _kernel_backend
from ray_tpu.ops.paged_attention import block_size

_LANES = 128
_MASKED = -1e30
# Queries and keys a block, heads a grid step: the one place the choice is
# made (PERF.md section 6, PR 58, has the sweep on the chip that chose them;
# the tests set smaller ones here to walk several blocks at small sizes).
_BLOCK_Q, _BLOCK_K, _HEADS = 512, 1024, 8
_VMEM_LIMIT_BYTES = 96 * 2 ** 20

# The kernel's module names no file (kernel_source.py says why).
kernel_source.exclude(__file__)


def _compiled(S: int, T: int, dn: int, dv: int) -> bool:
    """Whether the kernel is compiled for a chunk of S queries over T
    positions with heads of ``dn`` unrotated key columns and ``dv`` value
    columns, or interpreted: compiled where the backend has a compiler for
    it, a head is a run of whole lane tiles of a token-major row and blocks
    of whole tiles divide S and T (an int8 mask tile is 32 x 128)."""
    return (_kernel_backend() and dn % _LANES == 0 and dv % _LANES == 0
            and block_size(S, _BLOCK_Q, 32) > 0
            and block_size(T, _BLOCK_K, _LANES) > 0)


def _visible(i, start, length, block_q: int, block_k: int):
    """Blocks of keys the ``i``-th block of queries reads: as far as the
    position of its last real query; none if its queries are all padding.
    int32 scalars in, by ``lax`` primitives alone: the kernel's index maps
    run this, and a ``jnp`` function of scalars (``//``, ``where``) is
    traced once a process, wherever it is first met, and would bring that
    caller's source lines into the kernel's module (kernel_source.py)."""
    end = start + jax.lax.min((i + 1) * block_q, length)     # one past it
    blocks = jax.lax.div(end + (block_k - 1), jnp.int32(block_k))
    return jax.lax.select(i * block_q < length, blocks,
                          jnp.zeros_like(blocks))


def latent_chunk_attention(q_nope, q_rope, k, v, k_rope, keep, start, length,
                           *, sm_scale: float):
    """Attention of a chunk's queries ``q_nope`` [S, N, dn], ``q_rope`` [S,
    N, dr] (positions ``start ..``, ``length`` of them real) over the
    sequence's T positions: ``k`` [T, N x dn] and ``v`` [T, N x dv] expanded
    per head (head ``n`` a row's columns ``n x dn ..``), ``k_rope`` [T, dr]
    shared; ``keep`` [S, T] bool, the positions a query reads (the causal
    bound within it), or None: every position up to the query's own.
    Returns [S, N, dv] in ``v``'s type.  The blocks are the largest whole
    tiles under ``_BLOCK_Q`` / ``_BLOCK_K`` that divide S / T (the
    interpreter takes any divisor), the heads a step likewise of N."""
    S, N, dn = q_nope.shape
    T, dv = k.shape[0], v.shape[1] // N
    interpret = not _compiled(S, T, dn, dv)
    bounds = jnp.stack([jnp.asarray(start, jnp.int32),
                        jnp.asarray(length, jnp.int32)])
    out = _walk(q_nope.reshape(S, N * dn), jnp.swapaxes(q_rope, 0, 1),
                k, v, k_rope,
                None if keep is None else keep.astype(jnp.int8), bounds,
                sm_scale=float(sm_scale),
                qb=block_size(S, _BLOCK_Q, 1 if interpret else 32),
                kb=block_size(T, _BLOCK_K, 1 if interpret else _LANES),
                G=block_size(N, _HEADS), interpret=interpret)
    return out.reshape(S, N, dv)


# jitted and inlined where it is called, as ``paged_read._walk`` is: a
# model's programs trace the kernel once a shape, not once a group of layers.
@functools.partial(jax.jit, static_argnames=("sm_scale", "qb", "kb", "G",
                                             "interpret"), inline=True)
def _walk(q_nope, q_rope, k, v, k_rope, keep, bounds, *, sm_scale: float,
          qb: int, kb: int, G: int, interpret: bool):
    N, S, dr = q_rope.shape
    T = k.shape[0]
    dn, dv = k.shape[1] // N, v.shape[1] // N
    steps = T // kb
    masked = keep is not None

    def kernel(bounds_ref, qn_ref, qr_ref, k_ref, v_ref, kr_ref, *refs):
        keep_ref = refs[0] if masked else None
        o_ref, m_scr, l_scr, acc_scr = refs[masked:]
        i, j = pl.program_id(0), pl.program_id(2)
        begin = bounds_ref[0]

        @pl.when(j == 0)
        def _():
            m_scr[...] = jnp.full(m_scr.shape, _MASKED, jnp.float32)
            l_scr[...] = jnp.zeros(l_scr.shape, jnp.float32)
            acc_scr[...] = jnp.zeros(acc_scr.shape, jnp.float32)

        @pl.when(j < _visible(i, begin, bounds_ref[1], qb, kb))
        def _():
            if masked:
                seen = keep_ref[...].astype(jnp.int32) != 0
            else:
                seen = j * kb + jax.lax.broadcasted_iota(
                    jnp.int32, (qb, kb), 1) <= begin + i * qb \
                    + jax.lax.broadcasted_iota(jnp.int32, (qb, kb), 0)
            kr = kr_ref[...]
            for h in range(G):
                keys, vals = pl.ds(h * dn, dn), pl.ds(h * dv, dv)
                scores = (jax.lax.dot_general(
                    qn_ref[:, keys], k_ref[:, keys], (((1,), (1,)), ((), ())),
                    preferred_element_type=jnp.float32)
                    + jax.lax.dot_general(
                        qr_ref[h], kr, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32)) * sm_scale
                scores = jax.lax.select(seen, scores,
                                        jnp.full_like(scores, _MASKED))
                m = m_scr[h, :, 0:1]
                m_new = jnp.maximum(m, jnp.max(scores, axis=1, keepdims=True))
                # (a query that sees nothing of this block adds nothing)
                probs = jax.lax.select(seen, jnp.exp(scores - m_new),
                                       jnp.zeros_like(scores))
                fade = jnp.exp(m - m_new)
                l_scr[h, :, 0:1] = l_scr[h, :, 0:1] * fade \
                    + jnp.sum(probs, axis=1, keepdims=True)
                acc_scr[:, vals] = acc_scr[:, vals] * fade + jnp.dot(
                    probs.astype(v_ref.dtype), v_ref[:, vals],
                    preferred_element_type=jnp.float32)
                m_scr[h, :, 0:1] = m_new

        @pl.when(j == steps - 1)
        def _():
            for h in range(G):
                vals = pl.ds(h * dv, dv)
                o_ref[:, vals] = (acc_scr[:, vals] / jnp.maximum(
                    l_scr[h, :, 0:1], 1e-30)).astype(o_ref.dtype)

    def held(i, j, bounds):
        """The block of keys step ``j`` of the ``i``-th block of queries
        has in hand: its own, or the walk's last."""
        return jax.lax.clamp(
            jnp.int32(0), _visible(i, bounds[0], bounds[1], qb, kb) - 1, j)

    call = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((S, N * dv), v.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            in_specs=[
                pl.BlockSpec((qb, G * dn), lambda i, g, j, b: (i, g)),
                pl.BlockSpec((G, qb, dr), lambda i, g, j, b: (g, i, 0)),
                pl.BlockSpec((kb, G * dn),
                             lambda i, g, j, b: (held(i, j, b), g)),
                pl.BlockSpec((kb, G * dv),
                             lambda i, g, j, b: (held(i, j, b), g)),
                pl.BlockSpec((kb, dr),
                             lambda i, g, j, b: (held(i, j, b), 0))]
            + [pl.BlockSpec((qb, kb),
                            lambda i, g, j, b: (i, held(i, j, b)))] * masked,
            out_specs=pl.BlockSpec((qb, G * dv), lambda i, g, j, b: (i, g)),
            scratch_shapes=[pltpu.VMEM((G, qb, _LANES), jnp.float32),
                            pltpu.VMEM((G, qb, _LANES), jnp.float32),
                            pltpu.VMEM((qb, G * dv), jnp.float32)],
            grid=(S // qb, N // G, steps)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="latent_chunk")
    with kernel_source.nowhere():
        return call(bounds, q_nope, q_rope, k, v, k_rope,
                    *([keep] if masked else []))
