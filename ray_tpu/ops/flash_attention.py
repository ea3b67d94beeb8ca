"""Flash (blockwise, online-softmax) causal attention as Pallas TPU kernels.

The reference has no fused attention of its own (it defers to torch); on TPU
the memory-bound step is reading the [S, S] score matrix from HBM, so we
never materialize it.  All three kernels use a 3-D grid — (batch*heads,
out_block, streamed_block) — with the streamed operand (K/V for the q-side
kernels, Q/dO for the k-side kernel) delivered one VMEM tile per inner grid
step, so VMEM stays O(block) no matter the sequence length; Pallas
double-buffers the inner-dim DMAs automatically.  Online-softmax /
gradient accumulators live in f32 VMEM scratch across inner steps.

Backward is the FlashAttention-2 recurrence, also in Pallas — NOT a dense
vjp.  Residuals are q, k, v, o, lse (all O(S) off-chip).  Two kernels:

  * dq kernel    — grid over q blocks; streams K/V blocks, recomputes the
    probability tile from (q, k, lse) and accumulates dq.
  * dk/dv kernel — grid over k blocks; streams Q/dO blocks, recomputes the
    probability tile and accumulates dk and dv via dim-0 contractions
    (implicitly-transposed matmuls the MXU executes natively).

lse and D ride into the kernels as [*, seq, _LANES] tiles (row value
broadcast along a narrow minor dim) so they slice as native sublane column
vectors — the same layout trick as jax.experimental.pallas.ops.tpu
.flash_attention's l/m tensors, but 8 lanes wide instead of 128.

Causal skipping, at two granularities.  A grid block wholly above the
diagonal is jumped with `pl.when`.  Inside a live grid step each kernel
walks its [block_q, block_k] block in [_SUB_TILE, _SUB_TILE] sub-tiles: a
sub-tile above the diagonal is not computed, a run of sub-tiles below it is
one product with no iota/compare/select, and only the sub-tiles the
diagonal crosses are masked (`tile_plan` counts them: at S = 1024, where
one grid block is the whole sequence, 10 of 16 computed and 4 masked).  The
walk is static: Python loops over `_walk`'s pieces, so nothing is decided
on the chip but which of a few walks a grid step runs (`_runs`).  Where the
streamed grid dimension has one step (S <= 1024 with the default blocks)
the accumulators are values of that step and no scratch is allocated;
where it has several they are carried in scratch, and the forward, whose
statistics pay for every update, takes a grid block below the diagonal as
one piece.

Grouped queries, forward only: k and v may have G heads for q's N = G * rep,
and a query head reads its group's through the BlockSpec index map (``b //
rep``), so nothing is repeated in memory.  That is the served prefill's call
(``models/llama.py::llama_prefill`` on the rungs where
``ops/attention.py::resolve_attention`` says flash); a gradient through it
raises.

On the CPU backend the same kernels run in Pallas interpret mode, keeping
CPU tests honest.

Design analog: the reference defers attention to torch SDPA/flash-attn CUDA
kernels; this is the TPU-native replacement (SURVEY §5.7).
"""

from __future__ import annotations

import collections
import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops import kernel_source

# The kernels' modules name no file (kernel_source.py says why): a serving
# engine compiles the forward into its long prefill rungs at every start.
kernel_source.exclude(__file__)

_NEG_INF = -1e30
_LANES = 8     # minor-dim width of the lse/D carrier tensors
_SCR = 128     # lane width of VMEM scratch accumulators

# The smallest block the TPU compiler tiles.
_MIN_BLOCK = 8

# The edge of the sub-tiles a kernel walks its grid block in (see
# "sub-tiles" below).  Like the grid blocks of `_default_blocks`, this is
# the one place the choice is made; PERF.md section 6 (PR 45) has the chip
# sweep at S = 1024, head size 64 that chose it for all three kernels.
_SUB_TILE = 256


def _default_blocks(S: int, strict: bool = True) -> tuple:
    """The (block_q, block_k) a call without explicit blocks runs with:
    1024, halved until it divides S.  (1024, 1024) is what both training
    cells of the benchmark run (S = 1024): one grid step a head, its q, k,
    v resident for the step and walked in `_SUB_TILE` sub-tiles
    (``flash_fwd/dq/dkv_roofline`` 18.0 / 22.3 / 21.6 in PERF_LEDGER.jsonl
    up to PR 44, when a step computed the whole masked [1024, 1024] tile;
    PERF.md section 6, PR 45 has what the walk reads).  Smaller grid
    blocks pay ~0.35 us a grid step against ~2.5 us of work a head.  This
    is the one place the choice is made: a sweep on the chip
    (``scripts/flash_sweep.py``, which refuses to time another backend)
    changes this table in this file."""
    b = 1024
    while S % b:
        b //= 2
    if strict and b < _MIN_BLOCK:
        # Mosaic rejects sub-tile blocks with an opaque compile error, so
        # fail loudly here: the caller pads such a sequence length.
        # strict=False (interpret mode) permits them: the interpreter has
        # no tiling constraint.
        S_pad, pb = _suggest_blocks(S)
        raise ValueError(
            f"flash_attention: sequence length {S} only admits block sizes "
            f"({b}, {b}) < {_MIN_BLOCK}, which the TPU compiler rejects. "
            f"Pad the sequence to {S_pad} and use block_q={pb}, "
            f"block_k={pb} (mask the tail), or pass explicit "
            f"block_q/block_k >= {_MIN_BLOCK} that divide {S}.")
    return b, b


def _suggest_blocks(S: int) -> tuple:
    """For an S no TPU-legal block divides: the nearest padded sequence
    length and the largest block that divides it, (padded_S, block)."""
    pad = 128 if S > 16 else _MIN_BLOCK
    S_pad = -(-S // pad) * pad
    return S_pad, max([b for b in (128, 256, 512, 1024)
                       if b <= S_pad and S_pad % b == 0] or [pad])


# ------------------------------------------------------------- sub-tiles
#
# A grid block [block_q, block_k] is walked in sub-tiles [t_q, t_k].  With
# ``rel`` = (first query row) - (first key column) of a tile, the causal
# mask keeps s[r, c] where r + rel >= c, so a tile is
#   dead    if rel + t_q - 1 < 0     (every column lies ahead of every row),
#   plain   if rel >= t_k - 1        (no column lies ahead of any row),
#   masked  otherwise                (the diagonal crosses it).
# The same two tests sort the grid blocks, by their offset ``d = q_start -
# k_start``: ``d`` takes few values where the diagonal crosses a block, so
# each kernel is built with one static walk per such offset and one for the
# blocks below the diagonal; which walk a grid step runs is a ``pl.when``
# on its program ids.

_DEAD = "dead"


def _dead(rel, t_q: int):
    return rel + t_q - 1 < 0


def _plain(rel, t_k: int):
    return rel >= t_k - 1


def _sub_tiles(block_q: int, block_k: int) -> tuple:
    """(t_q, t_k) a grid block is walked in: ``_SUB_TILE``, or the largest
    power-of-two share of it that divides the block."""
    return math.gcd(block_q, _SUB_TILE), math.gcd(block_k, _SUB_TILE)


def _pieces(rels: list, t: int, t_q: int, t_k: int) -> list:
    """What a kernel computes of one row or column of a grid block's
    sub-tiles, ``rels`` their ``rel`` in order (None: nothing masked) and
    ``t`` their edge along it: pieces (start, size, rel).  A dead sub-tile
    is in no piece, a run of plain ones is ONE strip (rel None, one product
    as wide as the run), a masked one a piece of its own with the ``rel``
    its mask is made from."""
    pieces = []
    for i, rel in enumerate(rels):
        if rel is not None and _dead(rel, t_q):
            continue
        if rel is not None and not _plain(rel, t_k):
            pieces.append((i * t, t, rel))
        elif pieces and pieces[-1][2] is None:
            start, size, _ = pieces.pop()
            pieces.append((start, size + t, None))
        else:
            pieces.append((i * t, t, None))
    return pieces


def _walk(d: Optional[int], block_q: int, block_k: int, by: str) -> list:
    """The walk over a grid block at offset ``d`` (None: nothing masked):
    ``by`` "row", per sub-tile row (start, size, its pieces along the key
    columns); ``by`` "col", per sub-tile column (start, size, its pieces
    along the query rows, from the diagonal down).  Rows or columns with
    nothing to compute are left out."""
    t_q, t_k = _sub_tiles(block_q, block_k)
    nq, nk = block_q // t_q, block_k // t_k

    def rel(a, b):
        return None if d is None else d + a * t_q - b * t_k

    if by == "row":
        lines = [(a * t_q, t_q, _pieces([rel(a, b) for b in range(nk)],
                                        t_k, t_q, t_k)) for a in range(nq)]
    else:
        lines = [(b * t_k, t_k, _pieces([rel(a, b) for a in range(nq)],
                                        t_q, t_q, t_k)) for b in range(nk)]
    return [line for line in lines if line[2]]


def _walk_key(d: int, block_q: int, block_k: int, causal: bool):
    """Which walk the grid block at offset ``d`` gets: None (nothing
    masked), ``d`` itself (the diagonal crosses it) or ``_DEAD`` (none)."""
    if not causal or _plain(d, block_k):
        return None
    return _DEAD if _dead(d, block_q) else d


def _grid_walks(S: int, block_q: int, block_k: int, causal: bool) -> dict:
    """{walk key: the grid blocks of one head that run it}, the dead blocks
    left out: the walks a kernel over this grid is built with."""
    walks = collections.Counter(
        _walk_key(i * block_q - j * block_k, block_q, block_k, causal)
        for i in range(S // block_q) for j in range(S // block_k))
    walks.pop(_DEAD, None)
    return dict(walks)


def tile_plan(S: int, block_q: int, block_k: int, causal: bool) -> dict:
    """What each of the three kernels does for one head at these grid
    blocks: the sub-tile sizes and how many sub-tiles it computes, how many
    of those it masks, and how many the [S, S] scores hold.  The kernels
    run the same ``_walk`` of the same ``_grid_walks``."""
    t_q, t_k = _sub_tiles(block_q, block_k)
    computed = masked = 0
    for d, blocks in _grid_walks(S, block_q, block_k, causal).items():
        for _, _, pieces in _walk(d, block_q, block_k, "row"):
            computed += blocks * sum(size // t_k for _, size, _ in pieces)
            masked += blocks * sum(rel is not None for _, _, rel in pieces)
    return {"sub_q": t_q, "sub_k": t_k, "computed": computed,
            "masked": masked, "total": (S // t_q) * (S // t_k)}


def _runs(key, d, block_k: int, causal: bool):
    """Whether the grid block at offset ``d`` (traced, in a kernel) runs the
    walk ``key``: for every block, what ``_walk_key(d) == key`` says."""
    return (not causal or _plain(d, block_k)) if key is None else d == key


def _run_walk(walk, keys, causal, d, block_k):
    """Run ``walk(key)`` for the one of ``keys`` this grid step's block
    runs, ``d`` its offset; none, for a block above the diagonal."""
    for key in keys:
        pl.when(_runs(key, d, block_k, causal))(functools.partial(walk, key))


def _scores(q, k, sm_scale, rel):
    """q @ k^T * scale in f32 (the inputs' type on the MXU), masked where
    ``rel`` (static) says the diagonal crosses the tile."""
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * sm_scale
    if rel is not None:
        rows = rel + lax.broadcasted_iota(jnp.int32, s.shape, 0)
        cols = lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(rows >= cols, s, _NEG_INF)
    return s


# ---------------------------------------------------------------- forward

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, walks: tuple,
                causal: bool, sm_scale: float):
    # q_ref: [block_q, H]; k_ref/v_ref: [block_k, H] (streamed on grid dim 2)
    # o_ref: [block_q, H]; lse_ref: [block_q, _LANES]
    # scratch, where the key grid dimension has several steps: m/l
    # [block_q, _SCR], o [block_q, H], all f32, carried across them
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi, kb = pl.program_id(1), pl.program_id(2)
    num_kb = pl.num_programs(2)

    def finish(rows, m, l, acc):
        l = jnp.maximum(l, 1e-30)
        o_ref[rows, :] = (acc / l).astype(o_ref.dtype)
        lse_ref[rows, :] = jnp.broadcast_to(m + jnp.log(l),
                                            (m.shape[0], _LANES))

    if scratch:
        m_scr, l_scr, o_scr = scratch

        @pl.when(kb == 0)
        def _init():
            m_scr[:] = jnp.full((block_q, _SCR), _NEG_INF, jnp.float32)
            l_scr[:] = jnp.zeros((block_q, _SCR), jnp.float32)
            o_scr[:] = jnp.zeros((block_q, head_dim), jnp.float32)

    def update(q, pieces, old):
        # One softmax update for these query rows: their scores by piece,
        # the rows' maximum over all of them, then the sums.  The products
        # run in the input dtype (bf16-native on the MXU) with f32
        # accumulation; the statistics stay f32.
        scores = [_scores(q, k_ref[pl.ds(c0, w), :], sm_scale, rel)
                  for c0, w, rel in pieces]
        m = functools.reduce(
            jnp.maximum, [jnp.max(s, axis=-1, keepdims=True) for s in scores])
        if old is None:
            l = acc = 0.0
        else:
            m = jnp.maximum(old[0], m)
            alpha = jnp.exp(old[0] - m)
            l, acc = old[1] * alpha, old[2] * alpha
        for s, (c0, w, _) in zip(scores, pieces):
            p = jnp.exp(s - m)
            l = l + jnp.sum(p, axis=-1, keepdims=True)
            v = v_ref[pl.ds(c0, w), :]
            acc = acc + jnp.dot(p.astype(v.dtype), v,
                                preferred_element_type=jnp.float32)
        return m, l, acc

    def walk(d):
        lines = _walk(d, block_q, block_k, "row")
        if scratch and d is None:
            # Carried statistics pay for every update (they go through
            # the scratch), so a block with nothing to skip or mask is one
            # piece, one update (PERF.md section 6, PR 45: S = 4096).
            lines = [(0, block_q, [(0, block_k, None)])]
        for r0, h, pieces in lines:
            rows = pl.ds(r0, h)
            if scratch:
                old = m_scr[rows, 0:1], l_scr[rows, 0:1], o_scr[rows, :]
                m_scr[rows, 0:1], l_scr[rows, 0:1], o_scr[rows, :] = update(
                    q_ref[rows, :], pieces, old)
            else:
                finish(rows, *update(q_ref[rows, :], pieces, None))

    _run_walk(walk, walks, causal, qi * block_q - kb * block_k, block_k)

    if scratch:
        @pl.when(kb == num_kb - 1)
        def _finalize():
            finish(slice(None), m_scr[:, 0:1], l_scr[:, 0:1], o_scr[:])


def _layout_views(shape, layout):
    """(B, N, S, H, fold, unfold) for a q-shape under the given layout —
    the ONE place the fwd and bwd impls get their layout handling from."""
    if layout == "bnsh":
        B, N, S, H = shape

        def fold(x):
            return x.reshape(B * N, S, H)

        def unfold(x):
            return x.reshape(B, N, S, H)
    else:
        B, S, N, H = shape

        def fold(x):
            return x.transpose(0, 2, 1, 3).reshape(B * N, S, H)

        def unfold(x):
            return x.reshape(B, N, S, H).transpose(0, 2, 1, 3)
    return B, N, S, H, fold, unfold


def _kernel(body, S, block_q, block_k, causal, scale):
    """A kernel body bound to its static walks over these grid blocks."""
    return functools.partial(
        body, walks=tuple(_grid_walks(S, block_q, block_k, causal)),
        causal=causal, sm_scale=scale)


# jitted, and inlined where it is called (as ops/grouped_matmul.py's
# ``_tiled``): a serving engine compiles the forward into each of its long
# prefill rungs and runs the jitted prefill beside them, and traces the
# kernel once a shape.
@functools.partial(jax.jit, inline=True, static_argnames=(
    "causal", "block_q", "block_k", "sm_scale", "interpret", "layout"))
def _flash_fwd_impl(q, k, v, *, causal: bool, block_q: int, block_k: int,
                    sm_scale: Optional[float], interpret: bool,
                    layout: str = "bsnh"):
    """layout "bsnh": q,k,v [B, S, N, H] (folding costs a transpose).
    layout "bnsh": q,k,v [B, N, S, H] — folding to the kernel's
    [B*N, S, H] view is a FREE reshape; models that keep attention in
    bnsh (the GPT block does) skip ~25% of attention wall-clock that
    the bsnh relayouts cost at bench scale.
    k and v may have fewer heads than q, G with N = G * rep (grouped
    queries): a query head then reads its group's k and v through the
    index map, ``b // rep`` of the folded [B*G, S, H], and no copy of
    them is made.
    Returns (o in the input layout, lse [B*N, S] f32)."""
    B, N, S, H, _fold, _unfold = _layout_views(q.shape, layout)
    _, G, _, _, _fold_kv, _ = _layout_views(k.shape, layout)
    rep = N // G
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(H)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (
        f"seq {S} must divide blocks ({block_q},{block_k})")

    def kv_block(b, i, j):
        # lax.div, not ``//``: jnp's operators are jitted functions whose
        # cached jaxprs keep the source locations of whoever traced them
        # first in this process, and would carry those into the module
        return (b if rep == 1 else lax.div(b, jnp.int32(rep))), j, 0

    qf, kf, vf = _fold(q), _fold_kv(k), _fold_kv(v)
    call = pl.pallas_call(
        _kernel(_fwd_kernel, S, block_q, block_k, causal, scale),
        grid=(B * N, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), kv_block),
            pl.BlockSpec((None, block_k, H), kv_block),
        ],
        out_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
            jax.ShapeDtypeStruct((B * N, S, _LANES), jnp.float32),
        ],
        # the statistics are carried only over several key grid steps
        scratch_shapes=[
            pltpu.VMEM((block_q, _SCR), jnp.float32),
            pltpu.VMEM((block_q, _SCR), jnp.float32),
            pltpu.VMEM((block_q, H), jnp.float32),
        ] if S > block_k else [],
        interpret=interpret,
        name="flash_fwd",
    )
    with kernel_source.nowhere():
        of, lse = call(qf, kf, vf)
    return _unfold(of), lse[:, :, 0]


# ---------------------------------------------------------------- backward
#
# FlashAttention-2 recurrence.  With P = exp(S*scale - lse) the true softmax
# probabilities and D_i = sum_h dO_ih * O_ih:
#   dV = P^T dO;   dP = dO V^T;   dS = P * (dP - D) * scale
#   dQ = dS K;     dK = dS^T Q

def _p_and_ds(q, k, v, do, lse, delta, sm_scale, rel):
    """One sub-tile's probabilities and score gradients, [t_q, t_k] f32."""
    p = jnp.exp(_scores(q, k, sm_scale, rel) - lse)
    dp = lax.dot_general(                      # do @ v^T
        do, v, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)
    return p, p * (dp - delta) * sm_scale


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               *scratch, walks: tuple, causal: bool, sm_scale: float):
    # q_ref/do_ref/dq_ref: [block_q, H]; k_ref/v_ref: [block_k, H] (streamed);
    # lse_ref/delta_ref: [block_q, _LANES]; scratch, where the key grid
    # dimension has several steps: dq [block_q, H] f32, carried across them
    block_q, head_dim = q_ref.shape
    block_k = k_ref.shape[0]
    qi, kb = pl.program_id(1), pl.program_id(2)
    num_kb = pl.num_programs(2)

    if scratch:
        dq_scr, = scratch

        @pl.when(kb == 0)
        def _init():
            dq_scr[:] = jnp.zeros((block_q, head_dim), jnp.float32)

    def walk(d):
        for r0, h, pieces in _walk(d, block_q, block_k, "row"):
            rows = pl.ds(r0, h)
            q, do = q_ref[rows, :], do_ref[rows, :]
            lse, delta = lse_ref[rows, 0:1], delta_ref[rows, 0:1]
            dq = dq_scr[rows, :] if scratch else 0.0
            for c0, w, rel in pieces:
                k = k_ref[pl.ds(c0, w), :]
                _, ds = _p_and_ds(q, k, v_ref[pl.ds(c0, w), :], do, lse,
                                  delta, sm_scale, rel)
                dq = dq + jnp.dot(ds.astype(k.dtype), k,
                                  preferred_element_type=jnp.float32)
            if scratch:
                dq_scr[rows, :] = dq
            else:
                dq_ref[rows, :] = dq.astype(dq_ref.dtype)

    _run_walk(walk, walks, causal, qi * block_q - kb * block_k, block_k)

    if scratch:
        @pl.when(kb == num_kb - 1)
        def _finalize():
            dq_ref[:] = dq_scr[:].astype(dq_ref.dtype)


def _dkv_kernel(k_ref, v_ref, q_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, *scratch, walks: tuple, causal: bool,
                sm_scale: float):
    # k_ref/v_ref/dk_ref/dv_ref: [block_k, H]; q_ref/do_ref: [block_q, H]
    # (streamed); lse_ref/delta_ref: [block_q, _LANES]; scratch, where the
    # query grid dimension has several steps: dk, dv [block_k, H] f32
    block_k, head_dim = k_ref.shape
    block_q = q_ref.shape[0]
    ki, jb = pl.program_id(1), pl.program_id(2)
    num_qb = pl.num_programs(2)

    if scratch:
        dk_scr, dv_scr = scratch

        @pl.when(jb == 0)
        def _init():
            dk_scr[:] = jnp.zeros((block_k, head_dim), jnp.float32)
            dv_scr[:] = jnp.zeros((block_k, head_dim), jnp.float32)

    def walk(d):
        for c0, w, pieces in _walk(d, block_q, block_k, "col"):
            cols = pl.ds(c0, w)
            k, v = k_ref[cols, :], v_ref[cols, :]
            dk = dk_scr[cols, :] if scratch else 0.0
            dv = dv_scr[cols, :] if scratch else 0.0
            for r0, h, rel in pieces:
                rows = pl.ds(r0, h)
                q, do = q_ref[rows, :], do_ref[rows, :]
                p, ds = _p_and_ds(q, k, v, do, lse_ref[rows, 0:1],
                                  delta_ref[rows, 0:1], sm_scale, rel)
                # contract dim 0 of both: implicit transposes
                dv = dv + lax.dot_general(
                    p.astype(do.dtype), do, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
                dk = dk + lax.dot_general(
                    ds.astype(q.dtype), q, (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)
            if scratch:
                dk_scr[cols, :], dv_scr[cols, :] = dk, dv
            else:
                dk_ref[cols, :] = dk.astype(dk_ref.dtype)
                dv_ref[cols, :] = dv.astype(dv_ref.dtype)

    _run_walk(walk, walks, causal, jb * block_q - ki * block_k, block_k)

    if scratch:
        @pl.when(jb == num_qb - 1)
        def _finalize():
            dk_ref[:] = dk_scr[:].astype(dk_ref.dtype)
            dv_ref[:] = dv_scr[:].astype(dv_ref.dtype)


def _flash_bwd_impl(q, k, v, o, lse, g, *, causal: bool, block_q: int,
                    block_k: int, sm_scale: Optional[float],
                    interpret: bool, layout: str = "bsnh"):
    B, N, S, H, _fold, _unfold = _layout_views(q.shape, layout)
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(H)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (
        f"seq {S} must divide blocks ({block_q},{block_k})")
    qf, kf, vf, dof = _fold(q), _fold(k), _fold(v), _fold(g)
    # D_i = sum_h dO_ih O_ih — cheap elementwise reduce, leave it to XLA.
    delta = jnp.sum(dof.astype(jnp.float32) *
                    _fold(o).astype(jnp.float32), axis=-1)      # [B*N, S]
    lse_l = jnp.broadcast_to(lse[:, :, None], (B * N, S, _LANES))
    delta_l = jnp.broadcast_to(delta[:, :, None], (B * N, S, _LANES))

    dq_call = pl.pallas_call(
        _kernel(_dq_kernel, S, block_q, block_k, causal, scale),
        grid=(B * N, S // block_q, S // block_k),
        in_specs=[
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
        scratch_shapes=([pltpu.VMEM((block_q, H), jnp.float32)]
                        if S > block_k else []),
        interpret=interpret,
        name="flash_dq",
    )

    dkv_call = pl.pallas_call(
        _kernel(_dkv_kernel, S, block_q, block_k, causal, scale),
        grid=(B * N, S // block_k, S // block_q),
        in_specs=[
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, H), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((None, block_q, _LANES), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((None, block_k, H), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
            jax.ShapeDtypeStruct((B * N, S, H), q.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, H), jnp.float32),
            pltpu.VMEM((block_k, H), jnp.float32),
        ] if S > block_q else [],
        interpret=interpret,
        name="flash_dkv",
    )
    with kernel_source.nowhere():
        dqf = dq_call(qf, kf, vf, dof, lse_l, delta_l)
        dkf, dvf = dkv_call(kf, vf, qf, dof, lse_l, delta_l)

    return _unfold(dqf), _unfold(dkf), _unfold(dvf)


# ---------------------------------------------------------------- public API

def _dense_reference(q, k, v, causal, sm_scale):
    scale = sm_scale if sm_scale is not None else 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqnh,bknh->bnqk", q, k).astype(jnp.float32) * scale
    if causal:
        S = q.shape[1]
        mask = jnp.tril(jnp.ones((S, S), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bnqk,bknh->bqnh", p, v)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def flash_attention(q, k, v, causal: bool = True,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    sm_scale: Optional[float] = None,
                    interpret: Optional[bool] = None,
                    layout: str = "bsnh"):
    """Fused causal attention.

    layout "bsnh" (default): q,k,v [batch, seq, heads, head_dim].
    layout "bnsh": q,k,v [batch, heads, seq, head_dim] — the kernels'
    native view; models that produce attention inputs head-major skip
    the fold transposes entirely (~25% of attention time at short seq).
    block_q/block_k are the kernel's own parameters; left None they are
    what `_default_blocks` gives for S.
    k and v may have fewer heads than q (grouped queries: each run of
    ``heads // kv_heads`` query heads shares one), forward only: the
    served prefill's call.
    """
    return _forward(q, k, v, causal, block_q, block_k, sm_scale, interpret,
                    layout)[0]


def _resolve(q, block_q, block_k, interpret, layout):
    if interpret is None:
        interpret = not kernel_source.kernels_compiled()
    if block_q is None or block_k is None:
        S = q.shape[2 if layout == "bnsh" else 1]
        bq, bk = _default_blocks(S, strict=not interpret)
        block_q = block_q or bq
        block_k = block_k or bk
    return block_q, block_k, interpret


def _forward(q, k, v, causal, block_q, block_k, sm_scale, interpret, layout):
    bq, bk, interp = _resolve(q, block_q, block_k, interpret, layout)
    return _flash_fwd_impl(q, k, v, causal=causal, block_q=bq, block_k=bk,
                           sm_scale=sm_scale, interpret=interp,
                           layout=layout)


def _fwd(q, k, v, causal, block_q, block_k, sm_scale, interpret,
         layout="bsnh"):
    if k.shape != q.shape:
        raise NotImplementedError(
            f"flash_attention: no gradient is written for grouped heads "
            f"(q {list(q.shape)}, k/v {list(k.shape)}); repeat k and v up "
            f"to the query heads, as models/llama.py's training trunk does")
    out, lse = _forward(q, k, v, causal, block_q, block_k, sm_scale,
                        interpret, layout)
    return out, (q, k, v, out, lse)


def _bwd(causal, block_q, block_k, sm_scale, interpret, layout, res, g):
    q, k, v, o, lse = res
    bq, bk, interp = _resolve(q, block_q, block_k, interpret, layout)
    return _flash_bwd_impl(q, k, v, o, lse, g, causal=causal, block_q=bq,
                           block_k=bk, sm_scale=sm_scale, interpret=interp,
                           layout=layout)


flash_attention.defvjp(_fwd, _bwd)
